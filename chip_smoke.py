"""Drive the overlap index and the kNN-LM serving front once on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # sharded and routed layouts, four chips

One chip: build a VBM index over the paper's DB1 tracking shape (62,702 x
20; eps 6.0, MinPts 16, xi 0.4 / 0.8, c_max 250), with DBSCAN on the eps
kernels.  Search 100 queries at k = 10 and 100 in ``forest`` and ``all``
mode, over f32 and int8 bucket storage.  Ingest 4 x 512 points, search
again (the delta phase), run ``maintain()`` and search once more.  Then
serve 8 requests of 16 tokens through ``ServeEngine`` with the published
smollm-135m config (random weights) and a flat 65,536-key kNN-LM datastore.

Checks: every ``all``-mode answer agrees with a float64 numpy brute force
over the points the index stores (ids equal up to ties, squared distances
within ``sq_l2_tolerance``: (D + 2) * eps32 * (|q|^2 + |x|^2)); every compiled search plan holds a Pallas
kernel (``tpu_custom_call``); a 4-slot engine's tokens equal a 1-slot
engine's.  ``--chips 4`` runs only the sharded and routed layouts over the
same DB1 index and checks that searches before and after one ingest are
bitwise equal to the single-device layout in the same process.

Lines before the last are smoke numbers from one cold run, compilation
included, not benchmark numbers.  The last line is the JSON result.  Without
a TPU, or when any check fails, the script exits non-zero and prints no
result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import DB1_ROWS, tracking_dataset  # noqa: E402
from repro.api import (  # noqa: E402
    Config,
    IndexConfig,
    LayoutConfig,
    OverlapIndex,
    SearchConfig,
)
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.base import ModelConfig, RetrievalConfig  # noqa: E402
from repro.configs.smollm_135m import CONFIG as SMOLLM_135M  # noqa: E402
from repro.data.synthetic import embedding_datastore  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.serve.retrieval import build_flat_datastore  # noqa: E402

SEED = 0
F32_EPS = float(np.finfo(np.float32).eps)
KERNEL_MARK = "tpu_custom_call"  # how a Pallas kernel shows in compiled HLO


@dataclass(frozen=True)
class Sizes:
    """The run's scale; ``Sizes()`` is what the chip runs."""

    rows: int = DB1_ROWS
    queries: int = 100
    ks: tuple[int, ...] = (10, 100)
    ingest_batches: int = 4
    ingest_rows: int = 512
    datastore_keys: int = 65_536
    requests: int = 8
    prompt_tokens: int = 16
    new_tokens: int = 16
    model: ModelConfig = SMOLLM_135M


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class Clock:
    """Wall time of one phase, printed as a smoke number."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            say(f"{self.name} {time.perf_counter() - self.t0:.3f} s")


# --- the plain reference ----------------------------------------------------


def sq_l2_tolerance(q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """(Q, N) bound on |d2_f32 - d2_exact| for d2 = |q|^2 + |x|^2 - 2 q.x
    evaluated in f32: each of its D-term sums rounds to within about
    (D + 2) * eps32 of the pair's |q|^2 + |x|^2."""
    qq = (q.astype(np.float64) ** 2).sum(1)
    xx = (x.astype(np.float64) ** 2).sum(1)
    return (q.shape[1] + 2) * F32_EPS * (qq[:, None] + xx[None, :])


def brute_force(q: np.ndarray, x: np.ndarray, k: int):
    """float64 numpy kNN: (squared distances (Q, N), top-k ids (Q, k))."""
    q64, x64 = q.astype(np.float64), x.astype(np.float64)
    d2 = (q64 ** 2).sum(1)[:, None] + (x64 ** 2).sum(1)[None, :] - 2.0 * q64 @ x64.T
    d2 = np.maximum(d2, 0.0)
    ids = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return d2, ids


def stored_points(ix: OverlapIndex) -> np.ndarray:
    """The points the index holds, as it holds them: int8 forest members
    dequantized from the device copy, delta members as ingested."""
    x = ix.x_all.astype(np.float64)
    dev = ix.device
    if dev.bucket_scale is not None:
        ids = np.asarray(dev.bucket_ids).reshape(-1)
        xq = np.asarray(dev.bucket_x).reshape(ids.size, -1).astype(np.float64)
        scale = np.asarray(dev.bucket_scale).reshape(-1).astype(np.float64)
        live = ids >= 0
        x[ids[live]] = xq[live] * scale[live, None]
        if dev.bucket_offset is not None:  # the exact format (byte data)
            x[ids[live]] += np.asarray(dev.bucket_offset, np.float64)
    return x


def check_exact(name: str, res, q: np.ndarray, x: np.ndarray) -> None:
    """``all``-mode answers vs the float64 brute force over ``x``, within
    ``sq_l2_tolerance`` of each (query, point) pair:

    * each returned distance is its id's true distance;
    * rank by rank, the true distances of the returned ids equal the
      oracle's k smallest, so an id differs from the oracle's only where
      the two are tied within the tolerance.
    """
    k = res.ids.shape[1]
    d2, want = brute_force(q, x, k)
    tol = sq_l2_tolerance(q, x)
    rows = np.arange(len(q))[:, None]
    got = res.ids
    check((got >= 0).all(), f"{name}: -1 ids in an exact search")
    check(all(len(set(r)) == k for r in got.tolist()), f"{name}: repeated ids")
    err = np.abs(res.dists.astype(np.float64) ** 2 - d2[rows, got])
    check(
        (err <= tol[rows, got]).all(),
        f"{name}: a distance is off its id's true distance by {err.max():.3g}",
    )
    by_rank = np.argsort(d2[rows, got], axis=1, kind="stable")
    got_sorted = np.take_along_axis(got, by_rank, axis=1)
    gap = np.abs(d2[rows, got_sorted] - d2[rows, want])
    check(
        (gap <= np.maximum(tol[rows, got_sorted], tol[rows, want])).all(),
        f"{name}: an answer is farther than the oracle's by {gap.max():.3g}",
    )
    swaps = sum(len(set(a) - set(b)) for a, b in zip(got.tolist(), want.tolist()))
    ratio = err / tol[rows, got] * (q.shape[1] + 2)
    say(
        f"{name}: exact vs float64 oracle; max |d2 err| {err.max():.3g} "
        f"= {ratio.max():.3g} eps32 (|q|^2+|x|^2), tolerance "
        f"{q.shape[1] + 2} eps32 (|q|^2+|x|^2); ids outside the oracle's "
        f"top-{k} (ties) {swaps}"
    )


def recall(res, q: np.ndarray, x: np.ndarray) -> float:
    k = res.ids.shape[1]
    _, want = brute_force(q, x, k)
    hits = [len(set(a) & set(b)) for a, b in zip(res.ids.tolist(), want.tolist())]
    return float(np.sum(hits)) / want.size


# --- the device path ---------------------------------------------------------


def kernel_calls(ix: OverlapIndex, res, q: np.ndarray) -> int:
    """``tpu_custom_call``s (Pallas kernels) in the compiled plan that
    served ``res`` — 0 would mean the path fell back to the jnp oracle."""
    delta = ix.device_delta
    if delta is not None:
        from repro.stream.ingest import delta_view

        delta = delta_view(delta)
    operands = ix.backend.search_operands(ix.device)
    hlo = res.plan.executor.lower(operands, jnp.asarray(q), delta).compile().as_text()
    return hlo.count(KERNEL_MARK)


def db1_config(ds, *, quantize: bool = False, layout: LayoutConfig | None = None):
    return Config(
        index=IndexConfig(
            method="vbm", eps=ds.eps, min_pts=ds.min_pts, xi_min=ds.xi_min,
            xi_max=ds.xi_max, c_max=ds.c_max,
        ),
        search=SearchConfig(quantize=quantize),
        layout=layout or LayoutConfig(),
    )


def queries_and_inserts(x: np.ndarray, sizes: Sizes):
    """Readings near existing tracks: queries and the points to ingest."""
    rng = np.random.default_rng(SEED + 1)
    d = x.shape[1]
    q = x[rng.choice(len(x), sizes.queries)] + rng.normal(0, 0.8, (sizes.queries, d))
    n_new = sizes.ingest_batches * sizes.ingest_rows
    new = x[rng.choice(len(x), n_new)] + rng.normal(0, 0.8, (n_new, d))
    return q.astype(np.float32), new.astype(np.float32)


def search_all_ways(ix, name, q, sizes, *, modes=("forest", "all")) -> None:
    x = stored_points(ix)
    for k in sizes.ks:
        for mode in modes:
            tag = f"{name} k={k} {mode}"
            with Clock(f"{tag} first search (compile + run)"):
                res = ix.search(q, k=k, mode=mode)
            if mode == "all":
                check_exact(tag, res, q, x)
            else:
                say(f"{tag}: recall vs float64 oracle {recall(res, q, x):.4f}")
    # every plan of a stage runs the same executor body: check one of them
    calls = kernel_calls(ix, res, q)
    check(calls > 0, f"{tag}: compiled search plan holds no Pallas kernel")
    say(f"{tag}: tpu_custom_calls in compiled plan {calls}")
    with Clock(f"{tag} warm search"):
        ix.search(q, k=k, mode=mode)


def index_phase(sizes: Sizes) -> None:
    ds = tracking_dataset(sizes.rows)
    q, new = queries_and_inserts(ds.x, sizes)
    for quantize in (False, True):
        name = "int8" if quantize else "f32"
        with Clock(f"{name} build (DBSCAN + forest, {sizes.rows} x {ds.x.shape[1]})"):
            ix = OverlapIndex.build(ds.x, db1_config(ds, quantize=quantize))
        say(
            f"{name}: {ix.n_indexes} indexes, {ix.forest.n_buckets} buckets of "
            f"capacity {ix.forest.bucket_x.shape[1]}"
        )
        search_all_ways(ix, name, q, sizes)
        with Clock(f"{name} ingest {sizes.ingest_batches} x {sizes.ingest_rows}"):
            for b in np.split(new, sizes.ingest_batches):
                ix.ingest(b)
        search_all_ways(ix, f"{name}+delta", q, sizes)
        with Clock(f"{name} maintain"):
            report = ix.maintain()
        say(f"{name}: maintain rebuilt {len(report.triggers)} indexes")
        search_all_ways(ix, f"{name}+maintained", q, sizes, modes=("all",))


def serve_phase(sizes: Sizes) -> None:
    """Published smollm-135m widths and depth, random weights, retrieval over
    a flat datastore (the ``knn_topk`` kernel) at every decode step."""
    cfg = sizes.model.replace(
        retrieval=RetrievalConfig(enabled=True, datastore_size=sizes.datastore_keys)
    )
    model = Model(cfg)
    with Clock(f"{cfg.name} init"):
        params = model.init(jax.random.key(SEED))
    keys, values = embedding_datastore(sizes.datastore_keys, cfg.d_model, seed=SEED)
    ds = build_flat_datastore(keys, values % cfg.vocab_size)
    rng = np.random.default_rng(SEED + 2)
    prompts = rng.integers(0, cfg.vocab_size, (sizes.requests, sizes.prompt_tokens))
    max_len = sizes.prompt_tokens + sizes.new_tokens + 1

    def serve(slots: int) -> list[list[int]]:
        engine = ServeEngine(model, params, num_slots=slots, max_len=max_len, datastore=ds)
        for i, p in enumerate(prompts):
            engine.submit(
                Request(rid=i, prompt=p.astype(np.int32), max_new_tokens=sizes.new_tokens)
            )
        with Clock(f"serve {sizes.requests} requests on {slots} slot(s)"):
            done = engine.run()
        check(all(r.done for r in done), f"{slots}-slot engine left requests unfinished")
        say(f"{slots}-slot engine: {engine.steps} decode steps")
        return [r.out_tokens for r in sorted(done, key=lambda r: r.rid)]

    batched, alone = serve(4), serve(1)
    check(
        all(len(t) >= sizes.new_tokens for t in batched),
        "a request got fewer tokens than asked",
    )
    check(batched == alone, "4-slot engine tokens differ from the 1-slot engine's")
    say(f"serve: 4-slot tokens equal 1-slot tokens for {sizes.requests} requests")


def layouts_phase(sizes: Sizes, chips: int) -> None:
    """Sharded and routed layouts over the DB1 index vs single-device."""
    check(jax.device_count() >= chips, f"needs {chips} devices, found {jax.device_count()}")
    ds = tracking_dataset(sizes.rows)
    q, new = queries_and_inserts(ds.x, sizes)
    with Clock("single-device build"):
        single = OverlapIndex.build(ds.x, db1_config(ds))
    with tempfile.TemporaryDirectory() as tmp:
        path = single.save(os.path.join(tmp, "db1.npz"))
        others = {
            kind: OverlapIndex.load(path, layout=LayoutConfig(kind=kind, shards=chips))
            for kind in ("sharded", "routed")
        }
    batch = new[: sizes.ingest_rows]
    for stage in ("built", "after ingest"):
        if stage == "after ingest":
            want_ids = single.ingest(batch)
            for kind, ix in others.items():
                check(
                    np.array_equal(ix.ingest(batch), want_ids),
                    f"{kind}: ingest assigned other ids",
                )
        got = {}
        for k in sizes.ks:
            for mode in ("forest", "all"):
                want = single.search(q, k=k, mode=mode)
                for kind, ix in others.items():
                    tag = f"{kind} x{chips} {stage} k={k} {mode}"
                    with Clock(f"{tag} search (compile + run)"):
                        got[kind] = res = ix.search(q, k=k, mode=mode)
                    check(
                        np.array_equal(res.ids, want.ids)
                        and np.array_equal(
                            res.dists.view(np.uint32), want.dists.view(np.uint32)
                        ),
                        f"{tag}: not bitwise equal to single-device",
                    )
                    say(f"{tag}: bitwise equal to single-device")
        for kind, ix in others.items():
            calls = kernel_calls(ix, got[kind], q)
            check(calls > 0, f"{kind} {stage}: compiled plan holds no Pallas kernel")
            say(f"{kind} {stage}: tpu_custom_calls in compiled plan {calls}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run only the sharded/routed layouts against single-device",
    )
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing was run",
              file=sys.stderr)
        return 1
    say(f"compile cache {enable_compile_cache()}")
    say(f"device {dev.device_kind} x {jax.device_count()}, jax {jax.__version__}")
    sizes = Sizes()
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            index_phase(sizes)
            serve_phase(sizes)
        else:
            layouts_phase(sizes, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": jax.device_count(),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
