"""Device phases of the served range step: ``jax.named_scope`` names in
``hybrid_query`` (route, guard, ai, r, select), the AI path (predict,
refine, gather_ids) and the R path (traverse, refine, gather_ids).

The scopes are op-name metadata: the profiler reads them from each
operation's name stack, and they must not change a single output bit.
"""
import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build, device_tree as dt, labels
from repro.core.hybrid import hybrid_query
from repro.core.rtree import RTree
from repro.data import synth

# (enclosing scope, scope) pairs the served step must carry
SCOPES = [(None, "route"), (None, "guard"), (None, "select"),
          ("ai", "predict"), ("ai", "refine"), ("ai", "gather_ids"),
          ("r", "traverse"), ("r", "refine"), ("r", "gather_ids")]


@functools.lru_cache(maxsize=None)
def _world():
    """An MLP bank fitted to its training queries (the AI path answers
    some of them, the R path the rest) and 64 of those queries."""
    pts = synth.tweets_like(3000, seed=0)
    dtree = dt.flatten(RTree(max_entries=16).insert_all(pts))
    wl = labels.make_workload(dtree,
                              synth.synth_queries(pts, 2e-3, 160, seed=1))
    hyb, _ = build.fit_airtree(dtree, wl, kind="mlp", grid_sizes=(6,))
    return hyb, jnp.asarray(wl.queries[:64], jnp.float32)


def _step(use_kernel):
    # the narrow step as ``launch.serve.make_serve_fns`` builds it
    return jax.jit(functools.partial(hybrid_query, max_visited=16,
                                     max_results=64, use_kernel=use_kernel))


def _op_names(use_kernel) -> set:
    hyb, q = _world()
    text = _step(use_kernel).lower(hyb, q).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_served_step_names_every_phase(use_kernel):
    names = _op_names(use_kernel)
    for outer, scope in SCOPES:
        pat = (rf"(^|/){scope}(/|$)" if outer is None else
               rf"(^|/){outer}/(.*/)?{scope}(/|$)")
        assert any(re.search(pat, n) for n in names), (outer, scope)


@contextlib.contextmanager
def _no_scopes(monkeypatch):
    """``jax.named_scope`` as a no-op, with the traces made under it
    dropped on the way in and out."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        yield
    finally:
        monkeypatch.undo()
        jax.clear_caches()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_scopes_change_no_output_bit(use_kernel, monkeypatch):
    hyb, q = _world()
    scoped = jax.tree.map(np.asarray, _step(use_kernel)(hyb, q))
    assert scoped.used_ai.any() and not scoped.used_ai.all(), \
        "fixture too weak: one path answers every row"
    with _no_scopes(monkeypatch):
        step = _step(use_kernel)
        text = step.lower(hyb, q).compile().as_text()
        plain = jax.tree.map(np.asarray, step(hyb, q))
    assert "gather_ids" not in text     # the scopes really were off
    for f in type(scoped)._fields:
        a, b = getattr(scoped, f), getattr(plain, f)
        assert a.dtype == b.dtype and a.shape == b.shape, f
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8),
                                      err_msg=f)
