"""The serving exec cache, bucketed batching, and the unified bind API.

Covers the cache key mechanics (hit on same-bucket repeat, one bind
shared across buckets, miss + rebind on pruning-mask change, LRU
eviction), bucket selection boundaries (batch 9 -> bucket 32), the
staleness guard through cached execs, the batcher's flush policies, and
``SparseConvExec.report`` consistency vs the individual accounting
methods.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HAPMConfig, apply_masks, hapm_element_masks,
                        hapm_epoch_update, hapm_init)
from repro.launch.exec_cache import (DEFAULT_BUCKETS, BucketBatcher,
                                     CacheEntry, ExecCache, arch_fingerprint,
                                     bucket_for)
from repro.launch.serve_cnn import CnnServer, simulate_trace
from repro.models import cnn
from repro.sparse.conv_plan import mask_fingerprint

N_CU = 4


def _tiny(target=0.5, seed=0):
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
    params, state = cnn.init(jax.random.PRNGKey(seed), cfg)
    params = jax.tree_util.tree_map_with_path(
        lambda p, l: l / jnp.std(l) * 0.1 if cnn.is_conv_weight(p, l) else l,
        params)
    specs = cnn.conv_group_specs(params, N_CU)
    hcfg = HAPMConfig(target, 1)
    st = hapm_epoch_update(hapm_init(specs, hcfg), specs, params, hcfg)
    return cfg, apply_masks(params, hapm_element_masks(specs, st)), state


@pytest.fixture(scope="module")
def tiny():
    return _tiny(0.5)


@pytest.fixture(scope="module")
def served(tiny):
    """One warmed server shared by the read-only cache tests."""
    cfg, pruned, state = tiny
    server = CnnServer(pruned, state, cfg,
                       spec=cnn.ExecSpec(n_cu=N_CU), buckets=(1, 2))
    server.warmup()
    return server


# --------------------------------------------------------------- buckets
def test_bucket_selection_boundaries():
    assert bucket_for(1) == 1
    assert bucket_for(2) == 8
    assert bucket_for(8) == 8
    assert bucket_for(9) == 32          # the boundary the issue names
    assert bucket_for(32) == 32
    assert bucket_for(33) == 128
    assert bucket_for(128) == 128
    with pytest.raises(ValueError, match="exceeds"):
        bucket_for(129)
    with pytest.raises(ValueError, match=">= 1"):
        bucket_for(0)
    assert bucket_for(3, buckets=(4, 2)) == 4   # unsorted input, smallest fit


def test_execspec_validation_and_hashability():
    with pytest.raises(ValueError, match="bm"):
        cnn.ExecSpec(bm=1.5)
    with pytest.raises(ValueError, match="n_cu"):
        cnn.ExecSpec(n_cu=0)
    # frozen + hashable: it is a cache-key component
    a, b = cnn.ExecSpec(quantized=True), cnn.ExecSpec(quantized=True)
    assert a == b and hash(a) == hash(b)
    assert cnn.ExecSpec(folded=True) != cnn.ExecSpec(folded=False)
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.quantized = False


# ------------------------------------------------------------- ExecCache
def test_exec_cache_lru_eviction_order():
    cache = ExecCache(capacity=2)
    e = lambda b: CacheEntry(exec_=None, fn=None, bucket=b)
    k1, k2, k3 = ("a", "m", "s", 1), ("a", "m", "s", 2), ("a", "m", "s", 3)
    cache.put(k1, e(1))
    cache.put(k2, e(2))
    assert cache.get(k1) is not None        # k1 now most-recently used
    cache.put(k3, e(3))                     # evicts k2, NOT k1
    assert k1 in cache and k3 in cache and k2 not in cache
    assert cache.evictions == 1
    assert cache.get(k2) is None            # counted as a miss
    assert (cache.hits, cache.misses) == (1, 1)
    with pytest.raises(ValueError, match=">= 1"):
        ExecCache(capacity=0)


def test_exec_cache_invalidate_is_surgical():
    cache = ExecCache(capacity=8)
    e = lambda: CacheEntry(exec_=None, fn=None, bucket=1)
    for arch, mask, bucket in [("a1", "m1", 1), ("a1", "m1", 8),
                               ("a1", "m2", 1), ("a2", "m1", 1)]:
        cache.put((arch, mask, "spec", bucket), e())
    # drop a1's entries except fingerprint m2; other arch untouched
    assert cache.invalidate("a1", keep_mask_fp="m2") == 2
    assert cache.keys() == [("a1", "m2", "spec", 1), ("a2", "m1", "spec", 1)]
    assert cache.invalidate("a2") == 1
    assert cache.invalidated == 3


def test_fingerprints():
    cfg, pruned, state = _tiny(0.5)
    masks = cnn.derive_group_masks(pruned, N_CU)
    assert mask_fingerprint(masks) == mask_fingerprint(dict(
        reversed(list(masks.items()))))          # order-insensitive
    deeper = cnn.derive_group_masks(_tiny(0.75)[1], N_CU)
    assert mask_fingerprint(masks) != mask_fingerprint(deeper)
    # pytree form (HAPMState.group_masks-shaped) hashes the same pattern
    # class: binarized, so scores vs {0,1} masks agree
    assert mask_fingerprint({"c": {"w": np.array([1.0, 0.0, 2.0])}}) == \
        mask_fingerprint({"c": {"w": np.array([3.0, 0.0, 1.0])}})
    # arch fingerprint: values don't matter, shapes/config do
    assert arch_fingerprint(cfg, pruned) == arch_fingerprint(
        cfg, jax.tree_util.tree_map(lambda l: l * 0, pruned))
    assert arch_fingerprint(cfg, pruned) != arch_fingerprint(
        dataclasses.replace(cfg, quantized=True), pruned)


# ------------------------------------------------------- server + cache
def test_cache_hit_on_same_bucket_repeat(served):
    x = np.random.RandomState(0).rand(1, 16, 16, 3).astype(np.float32)
    h0, m0, b0 = served.cache.hits, served.cache.misses, served.cache.binds
    np.asarray(served.infer(x))
    np.asarray(served.infer(x))
    assert served.cache.hits == h0 + 2
    assert served.cache.misses == m0
    assert served.cache.binds == b0        # no rebind, no re-jit


def test_one_bind_shared_across_buckets(tiny):
    cfg, pruned, state = tiny
    server = CnnServer(pruned, state, cfg,
                       spec=cnn.ExecSpec(n_cu=N_CU), buckets=(1, 2, 4))
    server.warmup()
    assert server.cache.binds == 1
    assert len(server.cache) == 3
    execs = {id(server.cache.get(k).exec_) for k in server.cache.keys()}
    assert len(execs) == 1                 # the very same bound exec


def test_infer_chunks_and_pads_to_buckets(served):
    # batch 3 on buckets (1, 2): chunks of 2 + 1, outputs concatenated in
    # order — bit-identical to fresh per-chunk forwards at the same
    # shapes, and matching an unbucketed batch-3 forward to float
    # tolerance (XLA picks shape-dependent reduction tilings, so crossing
    # batch shapes moves logits at the ulp level)
    cfg, rng = served.cfg, np.random.RandomState(1)
    x = rng.rand(3, 16, 16, 3).astype(np.float32)
    got = np.asarray(served.infer(x))
    assert got.shape[0] == 3
    ex = cnn.bind_execution(served.params, cfg, spec=served.spec)
    # reference must be jitted too: the server always runs jitted
    # programs, and eager op-by-op execution drifts at the ulp level
    fwd = jax.jit(lambda xx: cnn.apply(served.params, served.state, xx, cfg,
                                       train=False, sparse=ex)[0])
    np.testing.assert_array_equal(
        got, np.concatenate([np.asarray(fwd(x[:2])), np.asarray(fwd(x[2:]))]))
    np.testing.assert_allclose(got, np.asarray(fwd(x)),
                               rtol=1e-4, atol=1e-6)


def test_bit_identical_through_cache_at_every_bucket(served):
    cfg, rng = served.cfg, np.random.RandomState(2)
    for b in served.buckets:
        x = rng.rand(b, 16, 16, 3).astype(np.float32)
        ex = cnn.bind_execution(served.params, cfg, spec=served.spec)
        # jitted reference: same-shape jitted programs are bit-identical;
        # the eager path is not (op-by-op vs fused XLA)
        ref = jax.jit(lambda xx, ee=ex: cnn.apply(
            served.params, served.state, xx, cfg,
            train=False, sparse=ee)[0])(x)
        np.testing.assert_array_equal(np.asarray(served.infer(x)),
                                      np.asarray(ref))


def test_mask_change_invalidates_and_rebinds(tiny):
    cfg, pruned, state = tiny
    server = CnnServer(pruned, state, cfg,
                       spec=cnn.ExecSpec(n_cu=N_CU), buckets=(1, 2))
    server.warmup()
    old_fp = server.mask_fp
    deeper = _tiny(0.75)[1]
    assert server.update_masks(deeper) == 2       # both bucket entries
    assert server.mask_fp != old_fp
    m0, b0 = server.cache.misses, server.cache.binds
    x = np.random.RandomState(0).rand(1, 16, 16, 3).astype(np.float32)
    np.asarray(server.infer(x))                   # miss -> rebind
    assert (server.cache.misses, server.cache.binds) == (m0 + 1, b0 + 1)
    h0 = server.cache.hits
    np.asarray(server.infer(x))                   # steady again
    assert server.cache.hits == h0 + 1
    # no-op update (same arrays, same pattern) invalidates nothing
    assert server.update_masks(deeper) == 0


def test_noop_update_masks_on_folded_server_keeps_cache(tiny):
    # fold_batchnorm allocates fresh arrays every _install, so a folded
    # server comparing the *derived* tree would read every no-op update
    # as a change and flush the cache; the comparison must run on the
    # installed params/state leaves instead
    cfg, pruned, state = tiny
    server = CnnServer(pruned, state, cfg,
                       spec=cnn.ExecSpec(folded=True, n_cu=N_CU),
                       buckets=(1, 2))
    server.warmup()
    assert len(server.cache) == 2
    assert server.update_masks(pruned) == 0       # same arrays: no-op
    assert len(server.cache) == 2                 # nothing invalidated
    deeper = _tiny(0.75)[1]
    assert server.update_masks(deeper) == 2       # real change still flushes


def test_infer_empty_request(served, tiny):
    cfg = tiny[0]
    out = served.infer(jnp.zeros((0, 16, 16, 3), jnp.float32))
    assert out.shape == (0, cfg.num_classes)
    assert out.dtype == jnp.float32


def test_distinct_specs_distinct_entries(tiny):
    cfg, pruned, state = tiny
    cache = ExecCache(capacity=8)
    for spec in (cnn.ExecSpec(n_cu=N_CU),
                 cnn.ExecSpec(n_cu=N_CU, quantized=True)):
        s = CnnServer(pruned, state, cfg, spec=spec, buckets=(1,),
                      cache=cache)
        s.warmup()
    assert len(cache) == 2 and cache.binds == 2   # no cross-spec aliasing


def test_staleness_guard_through_cache(served, tiny):
    cfg, _, state = tiny
    exec_ = served.cache.get(served.bind_key + (1,)).exec_
    other = _tiny(0.5, seed=1)[1]                 # different weight arrays
    x = jnp.zeros((1, 16, 16, 3))
    with pytest.raises(ValueError, match="stale"):
        cnn.apply(other, state, x, cfg, train=False, sparse=exec_)


def test_bind_execution_rejects_quant_spec_misuse(tiny):
    cfg, pruned, state = tiny
    from repro.core import quant as Q
    with pytest.raises(ValueError, match="quantized=True"):
        cnn.bind_execution(pruned, cfg, spec=cnn.ExecSpec(n_cu=N_CU),
                           quant_spec=Q.QuantSpec())
    folded = cnn.fold_batchnorm(pruned, state, cfg)
    with pytest.raises(ValueError, match="plain-exec only"):
        cnn.bind_execution(
            folded, cfg,
            spec=cnn.ExecSpec(folded=True, quantized=True, n_cu=N_CU),
            quant_spec=Q.QuantSpec())


# -------------------------------------------------------------- batcher
def test_batcher_full_bucket_flushes_immediately():
    b = BucketBatcher(buckets=(1, 4, 8), max_wait_s=10.0)
    for _ in range(7):
        b.submit(1, now=0.0)
    assert b.poll(now=0.0) == []               # 7 < 8: wait for more
    b.submit(1, now=0.0)
    [(bucket, ids)] = b.poll(now=0.0)          # 8th fills the max bucket
    assert bucket == 8 and len(ids) == 8 and len(b) == 0


def test_batcher_deadline_drains_bucket_aligned():
    b = BucketBatcher(buckets=(1, 4, 8), max_wait_s=0.01)
    for _ in range(6):
        b.submit(1, now=0.0)
    assert b.poll(now=0.005) == []             # before the deadline
    released = b.poll(now=0.011)               # oldest waited past max_wait
    assert [r[0] for r in released] == [4, 1, 1]   # largest filled, then tail
    assert sum(len(ids) for _, ids in released) == 6
    assert len(b) == 0


def test_batcher_virtual_clock_trace():
    b = BucketBatcher(buckets=(1, 4), max_wait_s=0.01)
    # 4-image request at t=0 flushes immediately; straggler at t=0.02
    # waits out its deadline alone
    sim = simulate_trace(b, [(0.0, 4), (0.02, 1)], lambda bucket: 0.001)
    assert sim["requests"] == 2
    assert sim["images"] == 5
    assert sim["releases"] == {"1": 1, "4": 1}
    # latency is per *request* now: [0.001, 0.011] — p50 interpolates
    assert sim["p50_s"] == pytest.approx(0.006, abs=1e-6)
    assert sim["p99_s"] == pytest.approx(0.011, abs=1e-3)
    # both releases ran full: 5 images / 5 capacity, not 2/5 (the
    # request-counting bug this regression pins down)
    assert sim["mean_bucket_fill"] == pytest.approx(1.0)


def test_batcher_trace_multi_image_fill():
    # two 2-image requests pack one 4-bucket: fill counts images (4/4),
    # and an oversize 9-image head releases alone, chunked server-side
    # into ceil(9/4)=3 max-bucket calls (9/12 capacity)
    b = BucketBatcher(buckets=(1, 4), max_wait_s=0.01)
    sim = simulate_trace(b, [(0.0, 2), (0.0, 2)], lambda bucket: 0.001)
    assert (sim["requests"], sim["images"]) == (2, 4)
    assert sim["releases"] == {"4": 1}
    assert sim["mean_bucket_fill"] == pytest.approx(1.0)

    b = BucketBatcher(buckets=(1, 4), max_wait_s=0.01)
    sim = simulate_trace(b, [(0.0, 9)], lambda bucket: 0.001)
    assert (sim["requests"], sim["images"]) == (1, 9)
    assert sim["releases"] == {"4": 1}
    assert sim["mean_bucket_fill"] == pytest.approx(9 / 12)


# ------------------------------------------------------------- report()
def test_report_matches_individual_methods(tiny):
    cfg, pruned, _ = tiny
    ex = cnn.bind_execution(pruned, cfg, bind_kernels=False,
                            spec=cnn.ExecSpec(n_cu=N_CU))
    rep = ex.report(cfg, batch=2, per_layer=True)
    executed, dense = ex.step_counts(cfg, batch=2)
    live, total = ex.schedule_step_counts()
    assert (rep["executed_grid_steps"], rep["dense_grid_steps"]) == \
        (executed, dense)
    assert (rep["schedule_steps_live"], rep["schedule_steps_total"]) == \
        (live, total)
    assert rep["hbm_bytes"] == ex.hbm_bytes(cfg, 2)
    assert rep["hbm_bytes_implicit"] == ex.hbm_bytes(cfg, 2, implicit=True,
                                                     bm="auto")
    assert rep["hbm_bytes_materialized"] == ex.hbm_bytes(cfg, 2,
                                                         implicit=False,
                                                         bm=128)
    assert rep["padded_mac_utilization"] == ex.mac_utilization(cfg, batch=2)
    assert rep["bm_effective"] == ex.bm_effective(cfg, batch=2)
    per_layer = rep["per_layer"]
    assert set(per_layer) == {"/".join(p) for p, _, _ in
                              cnn.conv_layer_order(cfg)}
    assert sum(v["executed"] for v in per_layer.values()) == executed
    assert sum(v["hbm_implicit"] for v in per_layer.values()) == \
        rep["hbm_bytes_implicit"]
    # accounting-only exec: no kernels were bound
    assert all(v is None for v in ex.table.values())


def _kernel_grids(jaxpr, name=None):
    """(kernel name, grid) of every Pallas call in ``jaxpr``, named by the
    innermost enclosing ``conv_<layer>`` jit."""
    for e in jaxpr.eqns:
        inner = name
        if e.primitive.name in ("jit", "pjit") and str(
                e.params.get("name", "")).startswith("conv_"):
            inner = e.params["name"]
        if e.primitive.name == "pallas_call":
            yield name, tuple(e.params["grid_mapping"].grid)
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _kernel_grids(sub, inner)


# folds by layer: 2 for 8x8 outputs and 8 for 4x4 ones; none under
# activation_dsb; under a VMEM budget cut to the s1b0/conv1 window at one
# image, each fold shrinks to the largest whose windows fit it
GRID_CASES = {
    "streamed": {"conv0": 1, "s0b0/conv1": 1, "s0b0/conv2": 1,
                 "s1b0/conv1": 2, "s1b0/conv2": 2, "s1b0/proj": 2,
                 "s2b0/conv1": 8, "s2b0/conv2": 8, "s2b0/proj": 8},
    "activation_dsb": {},
    "budget_shrinks_the_fold": {"s1b0/conv2": 2, "s2b0/conv1": 2,
                                "s2b0/conv2": 4, "s2b0/proj": 4},
}


@pytest.mark.parametrize("case", list(GRID_CASES))
def test_report_grid_steps_equal_the_launched_grids(case, monkeypatch):
    """The report's grid steps are the grids the served program's kernels
    launch: per layer, M-blocks (whole images folded into one where an
    image's output is small) × live K-tiles, at the bucket's batch —
    also where the VMEM budget reduces a fold."""
    from repro.kernels import implicit_conv as IC
    if case == "budget_shrinks_the_fold":
        s1_window = IC.choose_m_block(8, 8)
        monkeypatch.setattr(IC, "SLAB_VMEM_BUDGET", IC.window_vmem_bytes(
            s1_window, 3, 3, 2, 128, 1))
    cfg = cnn.ResNetConfig(stages=(1, 1, 1), widths=(8, 16, 32),
                           image_size=16)
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    dsb = case == "activation_dsb"
    server = CnnServer(params, state, cfg, buckets=(8,), spec=cnn.ExecSpec(
        n_cu=N_CU, quantized=True, folded=True, streamed=not dsb,
        activation_dsb=dsb, dense_fallback=2.0))
    entry = server._entry_for(server.rungs[0], 8)
    jaxpr = jax.make_jaxpr(entry.fn)(jnp.zeros((8, 16, 16, 3))).jaxpr
    grids = dict(_kernel_grids(jaxpr))
    ex = entry.exec_
    rep = ex.report(cfg, batch=8, per_layer=True)
    assert len(grids) == len(cnn.conv_layer_order(cfg))
    assert rep["layers_implicit"] == len(grids)
    executed = 0
    for path, stride, feat in cnn.conv_layer_order(cfg):
        plan, layer = ex.plans[path], rep["per_layer"]["/".join(path)]
        n_m, n_n, n_s = grids[cnn.conv_kernel_name(path)]
        assert (n_n, n_s) == plan.idx.shape
        assert layer["executed"] == n_m * int(plan.cnt.sum())
        assert layer["dense"] == n_m * plan.tiles[0] * plan.tiles[1]
        o = -(-feat // stride)
        bpi = 1 if o <= 8 else -(-o * o // 128)
        assert layer["images_per_block"] * n_m == 8 * bpi
        assert layer["bm_effective"] == layer["images_per_block"] * min(
            o * o, 128)
        executed += n_m * int(plan.cnt.sum())
    assert rep["executed_grid_steps"] == executed
    assert rep["images_per_block"] == {
        "/".join(p): GRID_CASES[case].get("/".join(p[:-1]), 1)
        for p, _, _ in cnn.conv_layer_order(cfg)}
