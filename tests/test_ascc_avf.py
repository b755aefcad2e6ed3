"""ASCC (jaxpr static checker, §6.3) + active variable filter (§4.3,
Thm 4.1) + volatility model + change detector."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LGA, build_graph, pod_graph
from repro.core.active_filter import (ActiveVariableFilter,
                                      expand_active_pods, leaves_under)
from repro.core.ascc import is_static_execution, readonly_state_leaves
from repro.core.change_detector import ChangeDetector
from repro.core.volatility import (ConstantVolatility, FlipTracker,
                                   GBMVolatility, PriorVolatility,
                                   graph_features)


# ---------------------------------------------------------------------------
# ASCC
# ---------------------------------------------------------------------------

def test_ascc_identity_passthrough():
    def step(state, x):
        return {"a": state["a"], "b": state["b"] + x}, state["a"].sum()

    state = {"a": jnp.ones((4,)), "b": jnp.zeros((4,))}
    ro = readonly_state_leaves(step, state, jnp.ones((4,)))
    assert ro == {"a"}


def test_ascc_full_readonly_is_static():
    def eval_step(state, x):
        return state, (state["w"] * x).sum()

    state = {"w": jnp.ones((8,))}
    assert is_static_execution(eval_step, state, jnp.ones((8,)))


def test_ascc_mutation_not_static():
    def step(state, x):
        return {"w": state["w"] + x}, None

    state = {"w": jnp.ones((8,))}
    assert not is_static_execution(step, state, jnp.ones((8,)))
    assert readonly_state_leaves(step, state, jnp.ones((8,))) == set()


def test_ascc_100pct_precision_on_rewrite():
    """A leaf rewritten with identical values is NOT declared read-only
    (conservative: precision 100%, recall < 100% — paper Table 3)."""
    def sneaky(state, x):
        return {"w": state["w"] * 1.0}, None  # value-identical rewrite

    state = {"w": jnp.ones((8,))}
    ro = readonly_state_leaves(sneaky, state, jnp.ones((8,)))
    assert ro == set()  # false negative allowed; false positive never


# ---------------------------------------------------------------------------
# AVF
# ---------------------------------------------------------------------------

def _graph_and_pods():
    rng = np.random.default_rng(0)
    state = {
        "hot": {"w": rng.standard_normal((256, 8)).astype(np.float32)},
        "cold": {"w": rng.standard_normal((256, 8)).astype(np.float32)},
        "step": 0,
    }
    g = build_graph(state, chunk_bytes=1 << 10)
    asg = pod_graph(g, LGA())
    return state, g, asg


def test_leaves_under():
    _state, g, _ = _graph_and_pods()
    assert leaves_under(g, ["hot"]) == {"hot/w"}
    assert leaves_under(g, ["hot", "cold"]) == {"hot/w", "cold/w"}


def test_avf_readonly_excluded():
    _state, g, _ = _graph_and_pods()
    avf = ActiveVariableFilter()
    act = avf.active_leaves(g, readonly_paths={"cold/w"})
    assert act == {"hot/w"}


def test_avf_touched_intersection():
    _state, g, _ = _graph_and_pods()
    avf = ActiveVariableFilter()
    act = avf.active_leaves(g, touched_prefixes=["hot"])
    assert act == {"hot/w"}


def test_thm41_pod_expansion():
    _state, g, asg = _graph_and_pods()
    pods = expand_active_pods(asg, g, ["hot"])
    hot_pod = asg.pod_of_key(g, "hot/w")
    assert hot_pod in pods


# ---------------------------------------------------------------------------
# change detector
# ---------------------------------------------------------------------------

def test_change_detector_dirty_tracking():
    rng = np.random.default_rng(1)
    state = {"a": rng.standard_normal((512, 8)).astype(np.float32)}
    g = build_graph(state, chunk_bytes=1 << 10)
    cd = ChangeDetector(chunk_bytes=1 << 10)
    r1 = cd.detect(g)
    assert len(r1.dirty) == len(r1.digests)  # first sight: all dirty
    r2 = cd.detect(build_graph(state, chunk_bytes=1 << 10))
    assert not r2.dirty
    state["a"][100] += 1
    r3 = cd.detect(build_graph(state, chunk_bytes=1 << 10))
    assert len(r3.dirty) == 1


def test_change_detector_counts_partly_dirty_leaves():
    """Leaves with some chunks dirty and some clean: their dirty bytes and
    their whole size; a wholly dirty or clean leaf counts in neither."""
    rng = np.random.default_rng(3)
    state = {"part": rng.standard_normal((1024, 8)).astype(np.float32),
             "whole": rng.standard_normal((64, 4)).astype(np.float32),
             "clean": rng.standard_normal((512, 8)).astype(np.float32)}
    cd = ChangeDetector(chunk_bytes=1 << 10)
    first = cd.detect(build_graph(state, chunk_bytes=1 << 10))
    assert first.partly_dirty_bytes == first.bytes_in_partly_dirty == 0
    state["part"][:64] += 1          # 2 of its 32 chunks
    state["whole"] += 1              # its one chunk
    r = cd.detect(build_graph(state, chunk_bytes=1 << 10))
    assert len(r.dirty) == 3
    assert r.partly_dirty_bytes == 2 * 1024
    assert r.bytes_in_partly_dirty == state["part"].nbytes


def test_change_detector_inactive_inherits():
    rng = np.random.default_rng(2)
    state = {"a": rng.standard_normal((64, 8)).astype(np.float32),
             "b": rng.standard_normal((64, 8)).astype(np.float32)}
    g = build_graph(state, chunk_bytes=1 << 20)
    cd = ChangeDetector(chunk_bytes=1 << 20)
    cd.detect(g)
    # mutate b but declare only a active: the detector must NOT see it
    state["b"][0] += 1
    r = cd.detect(build_graph(state, chunk_bytes=1 << 20),
                  active_leaf_paths={"a"})
    assert not r.dirty
    assert r.skipped_chunks >= 1


# ---------------------------------------------------------------------------
# volatility
# ---------------------------------------------------------------------------

def test_gbm_learns_separable_rule():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((800, 10))
    y = (X[:, 0] > 0).astype(float)
    m = GBMVolatility(n_estimators=40).fit(X, y)
    pred = m.predict(X)
    acc = ((pred > 0.5) == (y > 0.5)).mean()
    assert acc > 0.9


def test_constant_and_prior_models():
    rng = np.random.default_rng(4)
    state = {"w": rng.standard_normal((32, 4)).astype(np.float32), "n": 3}
    g = build_graph(state)
    feats = graph_features(g)
    X = np.stack(list(feats.values()))
    assert (ConstantVolatility(0.0).predict(X) == 0).all()
    assert (ConstantVolatility(1.0).predict(X) == 1).all()
    p = PriorVolatility().predict(X)
    assert ((0 <= p) & (p <= 1)).all()


def test_flip_tracker_ema_converges():
    rng = np.random.default_rng(5)
    state = {"w": rng.standard_normal((32, 4)).astype(np.float32)}
    g = build_graph(state)
    tr = FlipTracker(beta=0.5)
    key = next(iter(n.key for n in g.chunk_nodes()))
    for _ in range(8):
        tr.observe(g, dirty_keys={key})
    assert tr.ema[key] > 0.95
    for _ in range(8):
        tr.observe(g, dirty_keys=set())
    assert tr.ema[key] < 0.05


def test_tracker_trains_gbm():
    rng = np.random.default_rng(6)
    state = {"hot": rng.standard_normal((64, 4)).astype(np.float32),
             "cold": rng.standard_normal((64, 4)).astype(np.float32)}
    g = build_graph(state)
    tr = FlipTracker()
    hot = {n.key for n in g.chunk_nodes() if n.path[0] == "hot"}
    for _ in range(10):
        tr.observe(g, dirty_keys=hot)
    model = tr.fit_gbm(n_estimators=20)
    feats = graph_features(g, tr.ema)
    hot_l = np.mean([model.predict_one(feats[k]) for k in hot])
    cold = {n.key for n in g.chunk_nodes() if n.path[0] == "cold"}
    cold_l = np.mean([model.predict_one(feats[k]) for k in cold])
    assert hot_l > cold_l
