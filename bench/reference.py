"""Plain brute-force answers that decide ``correct``.

Independent of the program: it imports nothing of ``repro`` and reads only
the configuration's points (from ``bench.gen``) and the queries.
``range_answers`` scans every point for each rectangle and returns the
ids (row numbers in the configuration's point array) of every point
inside it, edges included. Coordinates are compared in float32, the
precision the served index stores them in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# room per query for the ids of one block; a query holding more raises
ID_CAP = 4096
QUERY_BLOCK = 32


@functools.partial(jax.jit, static_argnames=("cap",))
def _range_block(px, py, q, cap: int):
    inside = ((px[None, :] >= q[:, 0:1]) & (px[None, :] <= q[:, 2:3])
              & (py[None, :] >= q[:, 1:2]) & (py[None, :] <= q[:, 3:4]))
    ids = jax.vmap(lambda row: jnp.nonzero(row, size=cap, fill_value=-1)[0]
                   )(inside)
    return inside.sum(axis=1), ids


def range_answers(points: np.ndarray, queries: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray]:
    """[N, 2] points, [Q, 4] (x0, y0, x1, y1) rectangles → CSR answers
    ``(offsets [Q + 1], ids)``, each query's ids ascending."""
    p = np.asarray(points, np.float32)
    px, py = jnp.asarray(p[:, 0]), jnp.asarray(p[:, 1])
    q = np.asarray(queries, np.float32)
    counts, rows = [], []
    for lo in range(0, q.shape[0], QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]
        n = qb.shape[0]
        qb = np.concatenate([qb, np.repeat(qb[-1:], QUERY_BLOCK - n, 0)])
        c, ids = _range_block(px, py, jnp.asarray(qb), cap=ID_CAP)
        c, ids = np.asarray(c)[:n], np.asarray(ids)[:n]
        if c.max(initial=0) > ID_CAP:
            raise ValueError(f"a query holds {c.max()} points, more than "
                             f"the reference's {ID_CAP}")
        counts.append(c)
        rows.extend(r[:k] for r, k in zip(ids, c))
    counts = np.concatenate(counts)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    ids = (np.concatenate(rows) if rows else np.zeros(0)).astype(np.int64)
    return offsets, ids

