"""The plain reference and the comparison that decides ``correct``.

The reference is a float64 numpy brute force over the points the index
holds, as the benchmark generated them.  It imports nothing of the program
and takes nothing the program made.  Copied in spirit from the bring-up
smoke check (``chip_smoke.py``: ``brute_force``, ``sq_l2_tolerance``).

Every error is measured in units of ``eps32 * (|q|^2 + |x|^2)`` for the
(query, point) pair concerned: the scale at which an f32 evaluation of
``|q|^2 + |x|^2 - 2 q.x`` rounds.  The numbers compared:

* ``answer_err``: per query, the larger of two gaps, over its k answers:
  between a returned distance (squared) and the true squared distance of
  the id returned beside it, and, rank by rank, between the true squared
  distance of the returned neighbour and the reference's k-th smallest at
  that rank, so a missed neighbour shows even when the returned distances
  are right for their ids;
* ``bad_ids``: queries with an id outside the stored set or an id repeated
  among its answers (exact: limit 0).

``control_search`` is the reference put in the program's place, one
precision step below the configuration's: the cross term in three bf16
passes (XLA's ``Precision.HIGH``), written out with ``reduce_precision`` so
that it rounds the same way on any backend.  (Written with a round trip
through ``bfloat16`` instead, XLA on the TPU may keep the excess precision
and drop the low parts.)
"""
from __future__ import annotations

import numpy as np

F32_EPS = float(np.finfo(np.float32).eps)
QUERY_BLOCK = 16  # queries per block of the (Q, N) distance matrix


def sq_norms(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a, np.float64)
    return (a * a).sum(1)


def sq_dists(q: np.ndarray, x: np.ndarray, xx: np.ndarray | None = None) -> np.ndarray:
    """(Q, N) float64 squared distances, |q|^2 + |x|^2 - 2 q.x, clipped at 0."""
    x64 = np.asarray(x, np.float64)
    d2 = np.asarray(q, np.float64) @ x64.T
    d2 *= -2.0
    d2 += sq_norms(x64) if xx is None else xx
    d2 += sq_norms(q)[:, None]
    return np.maximum(d2, 0.0, out=d2)


def compare_rows(q: np.ndarray, x: np.ndarray, ids: np.ndarray, dists: np.ndarray,
                 xx: np.ndarray | None = None) -> dict[str, np.ndarray]:
    """Per query (Q,): the numbers for answers ``(ids, dists)`` (Q, k) to
    queries ``q`` over the stored points ``x``.  A query whose ids are bad
    reads 0 on ``answer_err``."""
    n = len(x)
    k = ids.shape[1]
    xx = sq_norms(x) if xx is None else xx
    bad = ((ids < 0) | (ids >= n)).any(1)
    bad |= np.array([len(set(r)) < k for r in ids.tolist()], bool)
    out = {"bad_ids": bad.astype(np.float64), "answer_err": np.zeros(len(q))}
    for lo in range(0, len(q), QUERY_BLOCK):
        sel = np.arange(lo, min(lo + QUERY_BLOCK, len(q)))
        sel = sel[~bad[sel]]
        if not len(sel):
            continue
        d2 = sq_dists(q[sel], x, xx)
        rows = np.arange(len(sel))[:, None]
        got = ids[sel]
        unit = F32_EPS * (sq_norms(q[sel])[:, None] + xx[got])
        true_got = d2[rows, got]
        db = dists[sel].astype(np.float64)
        err = np.abs(db * db - true_got) / unit
        # the reference's k smallest squared distances, ascending
        best = np.sort(np.partition(d2, k - 1, axis=1)[:, :k], axis=1)
        by_rank = np.argsort(true_got, axis=1, kind="stable")
        gap = np.abs(np.take_along_axis(true_got, by_rank, 1) - best)
        gap /= np.take_along_axis(unit, by_rank, 1)
        out["answer_err"][sel] = np.maximum(err.max(1), gap.max(1))
    return out


def merge(readings: list[dict[str, float]]) -> dict[str, float]:
    """Worst reading of each number over several batches."""
    out: dict[str, float] = {}
    for r in readings:
        for name, v in r.items():
            out[name] = v + out.get(name, 0.0) if name == "bad_ids" else max(v, out.get(name, 0.0))
    return out


def check(numbers: dict[str, float], limits: dict[str, float]) -> dict[str, dict]:
    """Each number beside its limit; a number passes at or under it."""
    return {
        name: {"value": numbers[name], "limit": limits[name], "ok": numbers[name] <= limits[name]}
        for name in limits
    }


# --- the control: the reference in the program's place, one step lower -----


def _split_bf16(a):
    """f32 -> (hi, lo) bf16 parts, a ~= hi + lo."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def control_search(q: np.ndarray, x: np.ndarray, k: int):
    """Brute-force kNN in float32 with the cross term in three bf16 passes
    (hi*hi + hi*lo + lo*hi): returns (dists (Q, k), ids (Q, k)) as numpy."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(qj, xj):
        f32 = jnp.float32
        qh, ql = _split_bf16(qj)
        xh, xl = _split_bf16(xj)

        def dot(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (1,)), ((), ())), preferred_element_type=f32
            )

        cross = dot(qh, xh) + dot(qh, xl) + dot(ql, xh)
        d2 = jnp.sum(qj * qj, 1)[:, None] + jnp.sum(xj * xj, 1)[None, :] - 2.0 * cross
        neg, ids = jax.lax.top_k(-jnp.maximum(d2, 0.0), k)
        return jnp.sqrt(-neg), ids

    d, i = run(jnp.asarray(q, jnp.float32), jnp.asarray(x, jnp.float32))
    return np.asarray(d), np.asarray(i)
