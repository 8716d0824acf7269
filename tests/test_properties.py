"""Property-based tests (hypothesis) on system invariants."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.core as core
from repro.core.aggregation import FedAvgState, fedavg_oracle

settings.register_profile("ci", max_examples=40, deadline=None)
settings.load_profile("ci")


# ---------------------------------------------------------------------------
# FedAvg invariants
# ---------------------------------------------------------------------------

updates_strategy = st.lists(
    st.tuples(
        st.lists(st.floats(-100, 100, allow_nan=False), min_size=4, max_size=4),
        st.floats(0.1, 50.0),
    ),
    min_size=1, max_size=8,
)


@given(updates_strategy)
def test_fedavg_permutation_invariance(items):
    us = [np.asarray(u, np.float32) for u, _ in items]
    ws = [w for _, w in items]
    a = fedavg_oracle(us, ws)
    perm = np.random.default_rng(0).permutation(len(us))
    b = fedavg_oracle([us[i] for i in perm], [ws[i] for i in perm])
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


@given(updates_strategy)
def test_fedavg_result_in_convex_hull(items):
    us = [np.asarray(u, np.float32) for u, _ in items]
    ws = [w for _, w in items]
    out = fedavg_oracle(us, ws)
    lo = np.min(np.stack(us), axis=0)
    hi = np.max(np.stack(us), axis=0)
    assert np.all(out >= lo - 1e-3) and np.all(out <= hi + 1e-3)


@given(updates_strategy)
def test_eager_fold_equals_lazy_batch(items):
    """Cumulative averaging (eager) == batch averaging (lazy) exactly
    (the precondition for the paper's eager aggregation, §2.1)."""
    us = [np.asarray(u, np.float32) for u, _ in items]
    ws = [w for _, w in items]
    eager = FedAvgState()
    for u, w in zip(us, ws):
        eager.fold(u, w)
    got, _ = eager.result()
    np.testing.assert_allclose(got, fedavg_oracle(us, ws), rtol=1e-4, atol=1e-4)


@given(updates_strategy, st.integers(1, 6))
def test_hierarchical_merge_associativity(items, split):
    """Tree aggregation (partials merged) == flat aggregation for any
    partition of updates into leaf groups — the invariant that makes the
    aggregation hierarchy shape-free."""
    us = [np.asarray(u, np.float32) for u, _ in items]
    ws = [w for _, w in items]
    k = min(split, len(us))
    groups = np.array_split(np.arange(len(us)), k)
    root = FedAvgState()
    for g in groups:
        part = FedAvgState()
        for i in g:
            part.fold(us[i], ws[i])
        root.merge(part)
    got, _ = root.result()
    np.testing.assert_allclose(got, fedavg_oracle(us, ws), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# placement invariants
# ---------------------------------------------------------------------------

@given(
    st.integers(0, 150),
    st.lists(st.floats(1.0, 40.0), min_size=1, max_size=8),
    st.sampled_from(["bestfit", "worstfit", "firstfit"]),
)
def test_placement_never_exceeds_capacity(n_updates, caps, policy):
    nodes = {
        f"n{i}": core.NodeState(node=f"n{i}", max_capacity=c)
        for i, c in enumerate(caps)
    }
    p = core.place_updates(n_updates, nodes, policy=policy)
    for node, idxs in p.assignment.items():
        assert len(idxs) <= nodes[node].max_capacity + 1e-9
    placed = sum(len(v) for v in p.assignment.values())
    assert placed + len(p.overflow) == n_updates
    # no duplicates
    seen = [i for v in p.assignment.values() for i in v] + list(p.overflow)
    assert sorted(seen) == list(range(n_updates))


@given(
    st.integers(1, 100),
    st.integers(2, 8),
    st.floats(5.0, 40.0),
)
def test_bestfit_uses_no_more_nodes_than_worstfit(n_updates, n_nodes, cap):
    """Holds for HOMOGENEOUS capacities (the paper's testbed, §6.1).
    Hypothesis refuted the heterogeneous version (caps [5, 11], 6
    updates: BestFit fills the small bin first and spills, WorstFit fits
    everything in the big bin) — BestFit is a locality heuristic, not a
    bin-count optimum; recorded in EXPERIMENTS.md §Perf lessons."""
    mk = lambda: {
        f"n{i}": core.NodeState(node=f"n{i}", max_capacity=cap)
        for i in range(n_nodes)
    }
    best = core.place_updates(n_updates, mk(), policy="bestfit")
    worst = core.place_updates(n_updates, mk(), policy="worstfit")
    if not best.overflow and not worst.overflow:
        assert best.num_nodes_used <= worst.num_nodes_used


# ---------------------------------------------------------------------------
# hierarchy invariants
# ---------------------------------------------------------------------------

@given(
    st.dictionaries(
        st.sampled_from([f"n{i}" for i in range(6)]),
        st.floats(0.0, 50.0),
        min_size=1, max_size=6,
    ),
    st.integers(1, 5),
)
def test_hierarchy_covers_all_updates(queues, fan_in):
    planner = core.HierarchyPlanner(fan_in=fan_in)
    plan = planner.plan(queues, smooth=False)
    for node, q in queues.items():
        leaves = plan.per_node[node].num_leaves
        assert leaves * fan_in >= q - 1e-9     # capacity covers queue
        if q >= 1e-6:  # denormal q underflows ceil(q/fan) — not real load
            assert leaves >= 1
        assert leaves <= np.ceil(q / fan_in) + 1e-9  # no over-allocation


@given(st.lists(st.floats(0.0, 100.0), min_size=1, max_size=30))
def test_ewma_bounded_by_observations(obs):
    e = core.EWMA(0.7)
    for o in obs:
        v = e.update(o)
        assert min(obs) - 1e-6 <= v <= max(obs) + 1e-6


# ---------------------------------------------------------------------------
# quantization invariants
# ---------------------------------------------------------------------------

@given(st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                min_size=1, max_size=600))
def test_quantize_roundtrip_error_bound(vals):
    import jax.numpy as jnp
    from repro.kernels.quantize import QBLOCK, dequantize, quantize

    x = jnp.asarray(np.asarray(vals, np.float32))
    q, s = quantize(x, impl="jnp")
    back = dequantize(q, s, len(vals), impl="jnp")
    scales = np.repeat(np.asarray(s), QBLOCK)[: len(vals)]
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert np.all(err <= scales / 2 * 1.001 + 1e-6)
