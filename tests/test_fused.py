"""Fused single-sync save: kernel/engine/detector parity + sync counts.

The fused path must be bit-identical to the two-sync path at every
level: the fused digest+compare kernel vs the ref oracle, the fused
bucketed engine vs the plain one, `ChangeDetector(fused=True)` vs the
host compare, and whole-store manifests with `fused=True` vs
`fused=False`.  On top of parity, the sync-count contract: a warm
speculated sparse save issues exactly ONE blocking `jax.device_get`,
a forced mispredict pays exactly one corrective gather (≤ 2 total),
and checkout hands digest-matching leaves back as live arrays.
"""
import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import pytest

from repro.core.change_detector import ChangeDetector
from repro.core.checkpoint import Chipmink
from repro.core.graph import build_graph, chunk_slice, path_str
from repro.core.store import MemoryStore
from repro.kernels.batch import digest_leaves, digest_leaves_fused
from repro.kernels.fingerprint import fingerprint_words_cmp
from repro.kernels.ref import (fingerprint_words_cmp_ref,
                               fingerprint_words_ref)

from proptest import given, integers, sampled_from


class SyncCounter:
    """Counts blocking `jax.device_get` calls (the save sync metric)."""

    def __init__(self, monkeypatch):
        self.n = 0
        real = jax.device_get

        def counted(x):
            self.n += 1
            return real(x)

        monkeypatch.setattr(jax, "device_get", counted)


# --------------------------------------------------------------------------
# kernel parity: fused digest+compare vs the ref oracle
# --------------------------------------------------------------------------

@given(C=integers(1, 40), W=sampled_from([32, 128, 512, 2048]),
       rows=sampled_from([1, 4, 16]), seed=integers(0, 10_000),
       mode=sampled_from(["clean", "dirty", "sparse"]))
def test_cmp_kernel_matches_oracle(C, W, rows, seed, mode):
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 2**32, (C, W), dtype=np.uint32)
    lengths = rng.integers(1, W * 4 + 1, (C,), dtype=np.uint32)
    dig = np.asarray(fingerprint_words_ref(jnp.asarray(words),
                                           jnp.asarray(lengths), seed=seed))
    prev = dig.copy()
    if mode == "dirty":
        prev ^= np.uint32(1)
    elif mode == "sparse":
        flip = rng.random(C) < 0.3
        prev[flip, 0] ^= np.uint32(1)
    d, m = fingerprint_words_cmp(jnp.asarray(words), jnp.asarray(lengths),
                                 jnp.asarray(prev), seed=seed,
                                 tile=min(4096, W), rows=rows)
    dr, mr = fingerprint_words_cmp_ref(jnp.asarray(words),
                                       jnp.asarray(lengths),
                                       jnp.asarray(prev), seed=seed)
    assert np.array_equal(np.asarray(d), dig)
    assert np.array_equal(np.asarray(d), np.asarray(dr))
    assert np.array_equal(np.asarray(m), np.asarray(mr))
    expect = np.any(dig != prev, axis=1).astype(np.uint32)
    assert np.array_equal(np.asarray(m), expect)


# --------------------------------------------------------------------------
# fused engine: digest parity, dirty mask, payload byte-exactness
# --------------------------------------------------------------------------

def _leaves(rng, n, dtypes=("float32", "bfloat16", "int8")):
    out = []
    for i in range(n):
        dt = dtypes[int(rng.integers(0, len(dtypes)))]
        shape = (int(rng.integers(1, 200)), int(rng.integers(1, 9)))
        x = rng.standard_normal(shape)
        if dt == "bfloat16":
            out.append((f"l{i}", jnp.asarray(x, jnp.bfloat16)))
        elif dt == "int8":
            out.append((f"l{i}", jnp.asarray((x * 50), jnp.int8)))
        else:
            out.append((f"l{i}", jnp.asarray(x, jnp.float32)))
    return out


@given(n=integers(1, 5), seed=integers(0, 10_000),
       chunk=sampled_from([256, 1024]), frac=sampled_from([0.25, 0.5, 1.0]))
def test_fused_engine_parity_and_payload(n, seed, chunk, frac):
    """`frac` of the chunks speculated: leaves speculated at least half
    over are fetched whole, the others gathered row by row."""
    rng = np.random.default_rng(seed)
    items = _leaves(rng, n)
    base = digest_leaves(items, chunk_bytes=chunk)
    all_keys = set(base.keys)
    spec = {k for k in all_keys if rng.random() < frac}
    res, table = digest_leaves_fused(
        items, chunk_bytes=chunk, lookup=lambda k: None, spec_keys=spec)
    assert res.keys == base.keys
    assert np.array_equal(res.mat, base.mat)
    assert res.n_syncs == 1
    # no trusted previous digest anywhere: every device row forced dirty
    assert np.all(res.dirty == 1)
    # payload rows are byte-exact chunk payloads
    graph = build_graph({k: a for k, a in items}, chunk_bytes=chunk)
    by_key = {node.key: node for node in graph.chunk_nodes()}
    assert set(res.payload) == spec
    for key, got in res.payload.items():
        node = by_key[key]
        arr = graph.arrays[path_str(node.path)]
        want = np.asarray(chunk_slice(arr, node)).tobytes()
        assert got == want, key

    # second pass against the carried table: everything clean, still 1 sync
    res2, _ = digest_leaves_fused(
        items, chunk_bytes=chunk, table=table,
        lookup=lambda k: None, spec_keys=None)
    assert np.array_equal(res2.mat, base.mat)
    assert np.all(res2.dirty == 0)
    assert res2.n_syncs == 1


def test_fused_engine_host_rows_unknown():
    items = [("dev", jnp.arange(64, dtype=jnp.float32)),
             ("host", np.arange(64, dtype=np.float32))]
    res, _ = digest_leaves_fused(items, chunk_bytes=1 << 10,
                                 lookup=lambda k: None)
    dirty = {k: int(d) for k, d in zip(res.keys, res.dirty)}
    assert dirty["dev#[0]"] == 1          # device row, no prev: dirty
    assert dirty["host#[0]"] == -1        # host row: caller decides


def test_half_speculated_leaf_crosses_whole(monkeypatch):
    """Each leaf is judged on its own speculated chunks: one speculated
    at least half over crosses as the array itself (no device copy),
    one speculated sparsely crosses as gathered packed rows, and one
    with no speculated chunk does not cross at all."""
    items = [("a", jnp.arange(8 * 256, dtype=jnp.float32)),
             ("b", jnp.ones(4 * 256, jnp.float32)),
             ("c", jnp.arange(8 * 256, dtype=jnp.float32) * 3),
             ("d", jnp.zeros(4 * 256, jnp.float32))]
    fetched = []
    real = jax.device_get

    def spy(x):
        fetched.extend(jax.tree.leaves(x))
        return real(x)

    monkeypatch.setattr(jax, "device_get", spy)
    spec = {f"a#[{i}]" for i in range(8)} | {"b#[0]", "b#[1]", "c#[5]"}
    res, table = digest_leaves_fused(items, chunk_bytes=1 << 10,
                                     spec_keys=spec)
    (b,) = table.plan.buckets
    sent = [k for k, x in items if any(a is x for a in fetched)]
    assert sent == ["a", "b"]
    assert not any(a.shape == (b.padded_rows, b.width) for a in fetched)
    assert sum(a.shape == (1, b.width) for a in fetched) == 1   # c#[5]
    assert set(res.payload) == spec
    graph = build_graph(dict(items), chunk_bytes=1 << 10)
    for node in graph.chunk_nodes():
        if node.key in spec:
            arr = np.asarray(graph.arrays[path_str(node.path)])
            assert res.payload[node.key] == chunk_slice(arr, node).tobytes()


def test_speculation_sets_compile_nothing_new():
    """Speculated rows are gathered in pieces of one compiled shape a
    bucket, built when the plan first speculates: a later speculation
    set of any size compiles no program."""
    from jax._src import monitoring
    items = [(f"l{i}", jnp.arange(16 * 256, dtype=jnp.float32) + i)
             for i in range(4)]
    keys = digest_leaves(items, chunk_bytes=1 << 10).keys
    digest_leaves_fused(items, chunk_bytes=1 << 10, spec_keys=set(keys))
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    monitoring.register_event_duration_secs_listener(listen)
    try:
        for step in (3, 5, 7, 16):      # sparse sets of 22, 13, 10, 4 rows
            spec = set(keys[::step])
            res, _ = digest_leaves_fused(items, chunk_bytes=1 << 10,
                                         spec_keys=spec)
            assert set(res.payload) == spec
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


def test_dense_gather_fetches_whole_leaves(monkeypatch):
    """A leaf with at least half its chunks requested crosses as the
    array itself (no device copy); a sparsely requested leaf is sliced
    per chunk.  Bytes are the chunk payloads either way."""
    from repro.core.podding import batched_chunk_fetch
    dense = jnp.arange(8 * 256, dtype=jnp.float32)
    sparse = jnp.arange(8 * 256, dtype=jnp.float32) * 2
    graph = build_graph({"dense": dense, "sparse": sparse},
                        chunk_bytes=1 << 10)
    chunks = {n.key: n for n in graph.chunk_nodes()}
    want = [k for k in chunks if k.startswith("dense")] + \
        ["sparse#[1]", "sparse#[6]"]
    fetched_args = []
    real = jax.device_get

    def spy(x):
        fetched_args.extend(x)
        return real(x)

    monkeypatch.setattr(jax, "device_get", spy)
    get, n_syncs = batched_chunk_fetch(graph, [chunks[k] for k in want])
    assert n_syncs == 1
    assert any(a is graph.arrays["dense"] for a in fetched_args)
    assert len(fetched_args) == 3      # dense whole + 2 sparse slices
    for key in want:
        node = chunks[key]
        arr = np.asarray(graph.arrays[path_str(node.path)])
        assert get(node) == chunk_slice(arr, node).tobytes()


def test_save_body_drops_older_snapshot():
    """Once a newer save's body runs, the instance no longer references
    the arrays of the save before it: only the newest snapshot stays on
    the device."""
    import gc
    import weakref

    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 10)
    first = jnp.arange(4096, dtype=jnp.float32)
    ck.save({"w": first})
    ref = weakref.ref(first)
    del first
    seen = []
    detect = ck.detector.detect

    def spy(*args, **kw):
        gc.collect()
        seen.append(ref() is None)
        return detect(*args, **kw)

    ck.detector.detect = spy
    ck.save({"w": jnp.zeros(4096, jnp.float32)})
    assert seen == [True]


# --------------------------------------------------------------------------
# detector: fused vs host-compare parity over a mutation sequence
# --------------------------------------------------------------------------

@given(seed=integers(0, 10_000))
def test_detector_fused_matches_host_compare(seed):
    rng = np.random.default_rng(seed)

    def state(step):
        w = np.arange(3000, dtype=np.float32)
        w[:200] += step                   # chunk 0 of w flips every save
        return {"w": jnp.asarray(w),
                "b": jnp.full((100,), float(step // 2), jnp.float32),
                "host": np.arange(32, dtype=np.int32) + step % 3}

    fused = ChangeDetector(chunk_bytes=1 << 12, fused=True)
    plain = ChangeDetector(chunk_bytes=1 << 12, fused=False)
    for step in range(4):
        g1 = build_graph(state(step), chunk_bytes=1 << 12)
        g2 = build_graph(state(step), chunk_bytes=1 << 12)
        spec = ({k for k in fused.export_table() if rng.random() < 0.5}
                if step else None)
        r1 = fused.detect(g1, speculate=spec)
        r2 = plain.detect(g2)
        assert r1.digests == r2.digests
        assert r1.dirty == r2.dirty
        assert r1.n_syncs == 1
        if step:
            assert r1.fused_rows > 0
        # payload covers only speculated keys; hits+misses == dirty
        assert r1.n_spec_hits + r1.n_spec_misses == len(r1.dirty)
        assert r1.n_spec_hits == len({k for k in r1.dirty
                                      if k in r1.payload})


def test_detector_import_table_reseeds_fused():
    st = {"w": jnp.arange(2000, dtype=jnp.float32)}
    cd = ChangeDetector(chunk_bytes=1 << 12)
    r = cd.detect(build_graph(st, chunk_bytes=1 << 12))
    cd.import_table(dict(r.digests))
    assert cd._dev_table is None          # device mirror dropped
    r2 = cd.detect(build_graph(st, chunk_bytes=1 << 12))
    # re-seeded from the imported host table: fused path, nothing dirty
    assert r2.fused_rows == len(r2.digests)
    assert not r2.dirty and r2.n_syncs == 1


# --------------------------------------------------------------------------
# end-to-end: manifests bit-identical, sync counts, mispredicts
# --------------------------------------------------------------------------

def _mk_states(n=5):
    out = []
    w = np.arange(4000, dtype=np.float32)
    for i in range(n):
        w2 = w.copy()
        w2[:100] += i                     # sparse update: chunk 0 only
        out.append({"params": {"w": jnp.asarray(w2),
                               "frozen": jnp.ones((800,), jnp.float32)},
                    "step": i})
    return out


def test_manifests_bit_identical_fused_vs_twosync():
    sts = _mk_states()

    def run(fused):
        ck = Chipmink(MemoryStore(), chunk_bytes=1 << 12, fused=fused)
        tids = [ck.save(s) for s in sts]
        mans = []
        for t in tids:
            m = dict(ck.store.get_manifest(t))
            m.pop("stats", None)          # timing-only block
            mans.append(msgpack.packb(m, use_bin_type=True))
        pods = {meta["d"]: ck.store.get_pod(meta["d"])
                for t in tids
                for meta in ck.store.get_manifest(t)["pods"].values()}
        return mans, pods

    mf, pf = run(True)
    mn, pn = run(False)
    assert mf == mn
    assert pf == pn


def test_warm_sparse_save_is_single_sync(monkeypatch):
    sts = _mk_states()
    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 12)
    for s in sts[:3]:                     # warm up EMA + device table
        ck.save(s)
    counter = SyncCounter(monkeypatch)
    ck.save(sts[3])
    assert counter.n == 1                 # THE single-sync save
    s = ck.save_stats[-1]
    assert s["n_digest_syncs"] == 1
    assert s["n_gather_syncs"] == 0
    assert s["n_corrective_syncs"] == 0
    assert s["n_spec_misses"] == 0
    assert s["n_spec_hits"] == 1          # the one dirty chunk (w#[0])
    ck.save(sts[4])
    assert counter.n == 2                 # still one per save


def test_forced_mispredict_pays_one_corrective_sync(monkeypatch):
    sts = _mk_states()
    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 12)
    for s in sts[:4]:                     # 4 saves: frozen EMA ≈ 0.22
        ck.save(s)
    # mutate the historically-frozen leaf: its EMA sits under the
    # threshold, so speculation misses it and the save pays exactly one
    # corrective gather.
    st = dict(sts[4])
    st["params"] = dict(st["params"])
    st["params"]["frozen"] = jnp.zeros((800,), jnp.float32)
    counter = SyncCounter(monkeypatch)
    ck.save(st)
    s = ck.save_stats[-1]
    assert s["n_spec_misses"] > 0
    assert s["n_corrective_syncs"] == 1
    assert counter.n <= 2                 # digest fetch + ONE corrective
    # the mispredicted save still commits correct bytes
    out = ck.load(time_id=ck.save_stats[-1]["time_id"])
    assert np.array_equal(np.asarray(out["params"]["frozen"]),
                          np.zeros(800, np.float32))


def test_all_clean_save_is_single_sync(monkeypatch):
    sts = _mk_states()
    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 12)
    ck.save(sts[0])
    counter = SyncCounter(monkeypatch)
    ck.save(sts[0])                       # identical state: zero dirty
    assert counter.n == 1
    s = ck.save_stats[-1]
    assert s["n_dirty_chunks"] == 0
    assert s["n_gather_syncs"] == 0


# --------------------------------------------------------------------------
# checkout: leaf-level reuse + post-checkout fused single-sync
# --------------------------------------------------------------------------

def test_checkout_reuses_live_leaves(monkeypatch):
    frozen = jnp.arange(3000, dtype=jnp.float32)

    def st(i):
        return {"params": {"frozen": frozen,
                           "w": jnp.full((2000,), float(i), jnp.float32)},
                "step": i}

    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 12)
    t1 = ck.save(st(0))
    ck.save(st(1))
    out = ck.checkout(t1)
    cs = ck.last_checkout_stats
    assert cs.n_leaves_reused >= 1
    assert cs.n_pods_live > 0
    assert cs.n_pods_fetched < cs.n_pods
    # the digest-matching leaf comes back as the live array OBJECT
    assert out["params"]["frozen"] is frozen
    assert np.array_equal(np.asarray(out["params"]["w"]),
                          np.zeros(2000, np.float32))
    assert out["step"] == 0

    # first post-checkout save: import_table re-seeded the device table,
    # so the fused single-sync path runs — one blocking sync, no fallback.
    counter = SyncCounter(monkeypatch)
    ck.save({**st(0), "step": 7})
    s = ck.save_stats[-1]
    assert s["n_fused_rows"] > 0
    assert s["n_digest_syncs"] == 1
    assert s["n_corrective_syncs"] == 0
    assert counter.n == 1


def test_checkout_reuse_disabled_without_digest_match():
    # every leaf mutated between commits: nothing is reusable, checkout
    # still restores correct bytes through the normal path.
    def st(i):
        return {"w": jnp.full((2000,), float(i), jnp.float32), "step": i}

    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 12)
    t1 = ck.save(st(0))
    ck.save(st(1))
    out = ck.checkout(t1)
    assert ck.last_checkout_stats.n_leaves_reused == 0
    assert np.array_equal(np.asarray(out["w"]), np.zeros(2000, np.float32))
