"""Sharded live engine: mesh construction, partition/cache congruence,
tensor-parallel token identity, the mesh-aware Pallas decode kernel (and
its loud fallback for unsupported layouts), and the per-node executor
surface (counters, calibrated fits).

Device-gated tests need forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_sharded_engine.py

Under plain tier-1 (one device) they skip; the CI multi-device step runs
them at 8 devices.
"""
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import Job
from repro.engine import EngineConfig, EngineExecutor, InferenceEngine, make_tp_pods
from repro.engine.engine import _batch_axis
from repro.launch.mesh import make_mesh, pod_meshes
from repro.launch.partition import cache_pspecs, sanitize_specs
from repro.models import init_params
from repro.models import transformer as T

needs2 = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")
needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs >=8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")

#: one representative arch per cache family
FAMILY_ARCHS = {
    "dense": "qwen2-1.5b",
    "moe": "qwen2-moe-a2.7b",
    "ssm": "mamba2-130m",
    "hybrid": "zamba2-7b",
    "vlm": "qwen2-vl-7b",
    "audio": "whisper-large-v3",
}


def _mk(i, toks):
    return Job(job_id=i, prompt="x", prompt_tokens=list(toks),
               arrival_time=0.0)


def fake_mesh(shape, names):
    return SimpleNamespace(axis_names=names, devices=np.empty(shape))


# --------------------------------------------------------------------------- #
# Mesh construction
# --------------------------------------------------------------------------- #


def test_make_mesh_validates_shape_axes():
    with pytest.raises(ValueError):
        make_mesh((2, 4), ("model",))


def test_make_mesh_fails_loudly_without_devices():
    with pytest.raises(RuntimeError, match="device"):
        make_mesh((4096,), ("model",))


@needs8
def test_make_mesh_and_pod_meshes_disjoint():
    mesh = make_mesh((2, 4), ("data", "model"))
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "data": 2, "model": 4}
    pods = pod_meshes(mesh)
    assert len(pods) == 2
    seen = set()
    for pod in pods:
        ids = {d.id for d in np.asarray(pod.devices).ravel()}
        assert len(ids) == 4
        assert not ids & seen, "pods must own disjoint devices"
        seen |= ids
        assert pod.axis_names == ("model",)


def test_pod_meshes_requires_model_axis():
    with pytest.raises(ValueError, match="model"):
        pod_meshes(fake_mesh((2,), ("data",)))


# --------------------------------------------------------------------------- #
# Partition/cache congruence (every arch family)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("family,arch", sorted(FAMILY_ARCHS.items()))
def test_cache_pspecs_congruent_with_engine_cache(family, arch):
    """partition's cache spec tree must mirror the engine's actual cache
    pytree leaf-for-leaf: same structure, specs within leaf rank, the slot
    (batch) axis replicated, and only head/state axes on "model"."""
    cfg = get_config(arch).reduced()
    assert cfg.family == family
    eng = InferenceEngine(cfg, None, EngineConfig(max_slots=2, max_len=64))
    specs = cache_pspecs(cfg, eng.cache, None, model_size=2,
                         kv_shard="heads")
    spec_td = jax.tree_util.tree_structure(
        specs, is_leaf=lambda x: isinstance(x, P))
    cache_td = jax.tree_util.tree_structure(eng.cache)
    assert spec_td == cache_td, (
        f"{arch}: cache spec tree diverged from the engine cache pytree")
    leaves = jax.tree_util.tree_leaves_with_path(eng.cache)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(leaves, spec_leaves):
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)
        bax = _batch_axis(path, leaf.ndim)
        entries = tuple(spec) + (None,) * (leaf.ndim - len(spec))
        assert entries[bax] is None, (
            f"{arch}: slot axis {bax} of {path} must stay replicated, "
            f"got {spec}")
        for ax in entries:
            assert ax in (None, "model"), (path, spec)


@pytest.mark.parametrize("family,arch", sorted(FAMILY_ARCHS.items()))
def test_sanitized_cache_specs_divide_leaf_shapes(family, arch):
    """After sanitize_specs, every sharded axis divides its mesh-axis size
    (what device_put/jit will actually enforce)."""
    cfg = get_config(arch).reduced()
    cache = T.init_cache(cfg, 2, 64)
    mesh = fake_mesh((2,), ("model",))
    specs = sanitize_specs(
        mesh, cache_pspecs(cfg, cache, None, model_size=2,
                           kv_shard="heads"), cache)

    def check(spec, leaf):
        for dim, ax in enumerate(spec):
            if ax is not None:
                assert leaf.shape[dim] % 2 == 0, (spec, leaf.shape)
        return spec

    jax.tree_util.tree_map(check, specs, cache,
                           is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------- #
# Tensor-parallel token identity (the acceptance bar)
# --------------------------------------------------------------------------- #


def _run_identity(arch: str, tp: int):
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_slots=4, max_len=128, max_output=64, eos_id=-1)
    ref = InferenceEngine(cfg, params, ecfg)
    mesh = make_mesh((tp,), ("model",))
    sharded = InferenceEngine(cfg, params, ecfg, mesh=mesh)
    prompts = [[11, 22, 33, 44], [9, 8, 7], [301, 302, 303, 304, 305]]
    for eng in (ref, sharded):
        jobs = [_mk(i, p) for i, p in enumerate(prompts)]
        # window 1: two jobs -> compacted decode (gather/scatter sharded)
        t1, _ = eng.run_window(jobs[:2], 6)
        for j, t in zip(jobs, t1):
            j.generated.extend(t)
        # window 2: admit the third job (batched bucketed prefill) and run
        # the full width
        t2, _ = eng.run_window(jobs, 5)
        eng.result = (t1, t2)
    assert ref.result == sharded.result, (
        f"{arch} TP={tp}: sharded tokens diverged from single-device")


@needs2
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "qwen2-moe-a2.7b"])
def test_sharded_token_identity_tp2(arch):
    _run_identity(arch, tp=2)


@needs8
def test_sharded_token_identity_tp4_indivisible_kv():
    """qwen2-1.5b reduced has n_kv_heads=2: TP=4 cannot split the KV head
    axis, so sanitize_specs replicates KV while Q/FFN stay sharded — the
    mixed layout must still be token-identical."""
    _run_identity("qwen2-1.5b", tp=4)


@needs2
def test_preempt_resume_identical_under_sharding():
    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_slots=1, max_len=128, max_output=64, eos_id=-1)
    mesh = make_mesh((2,), ("model",))
    eng = InferenceEngine(cfg, params, ecfg, mesh=mesh)
    ref = InferenceEngine(cfg, params, ecfg)
    out = {}
    for name, e in (("ref", ref), ("sharded", eng)):
        job = _mk(0, [5, 6, 7])
        t1, _ = e.run_window([job], 5)
        job.generated.extend(t1[0])
        e.evict_job(job.job_id)
        t2, _ = e.run_window([job], 5)   # recompute-resume
        out[name] = t1[0] + t2[0]
    assert out["ref"] == out["sharded"]


@needs2
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "qwen2-moe-a2.7b"])
def test_chunked_prefill_identity_under_tp2(arch):
    """Chunked prefill on a TP pod emits the same greedy tokens as the
    unsharded one-shot engine (the chunk dispatch gathers/scatters the
    sharded slot cache)."""
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_slots=2, max_len=128, max_output=64, eos_id=-1)
    ref = InferenceEngine(cfg, params, ecfg)
    mesh = make_mesh((2,), ("model",))
    sharded = InferenceEngine(cfg, params, ecfg, mesh=mesh)
    prompt = [11 + k % 60 for k in range(23)]
    out = {}
    for name, eng, chunk in (("ref", ref, None), ("tp2", sharded, 6)):
        job = _mk(0, prompt)
        toks = []
        for _ in range(16):
            t, _ = eng.run_window([job], 4, prefill_chunk=chunk)
            job.generated.extend(t[0])
            toks.extend(t[0])
            if len(toks) >= 8:
                break
        out[name] = toks[:8]
    assert out["ref"] == out["tp2"], \
        f"{arch}: chunked prefill under TP mesh diverged"
    assert sharded.num_chunk_dispatches >= 4


@needs2
def test_swap_roundtrip_bit_exact_under_tp2():
    """offload_job pulls every shard to host (device_get) and restore_job
    re-shards it — the round-trip must be bit-exact under a TP mesh."""
    from repro.engine.engine import _gather_slots
    import jax.numpy as jnp

    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_slots=2, max_len=128, max_output=64, eos_id=-1)
    mesh = make_mesh((2,), ("model",))
    eng = InferenceEngine(cfg, params, ecfg, mesh=mesh)
    job = _mk(0, [5, 6, 7, 8])
    t1, _ = eng.run_window([job], 5)
    job.generated.extend(t1[0])
    slot = eng.slot_of[job.job_id]
    before = jax.device_get(
        _gather_slots(eng.cache, jnp.asarray([slot], jnp.int32)))
    assert eng.offload_job(job.job_id)
    new_slot = eng.restore_job(job)
    after = jax.device_get(
        _gather_slots(eng.cache, jnp.asarray([new_slot], jnp.int32)))
    for a, b in zip(jax.tree_util.tree_leaves(after),
                    jax.tree_util.tree_leaves(before)):
        assert np.array_equal(a, b)
    t2, _ = eng.run_window([job], 5)
    ref = InferenceEngine(cfg, params, ecfg)
    rj = _mk(0, [5, 6, 7, 8])
    r1, _ = ref.run_window([rj], 5)
    rj.generated.extend(r1[0])
    r2, _ = ref.run_window([rj], 5)
    assert t1[0] + t2[0] == r1[0] + r2[0]


# --------------------------------------------------------------------------- #
# Mesh-aware Pallas decode (DESIGN.md §11)
# --------------------------------------------------------------------------- #


@needs2
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "qwen2-moe-a2.7b"])
def test_pallas_token_identity_tp2(arch):
    """The tentpole bar: with a TP2 mesh and head-sharded KV,
    attn_impl='pallas' runs the shard_map'd kernel (no fallback, no
    warning) and emits greedy tokens bit-identical to BOTH the TP XLA
    path and the single-device Pallas path."""
    import warnings as W

    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((2,), ("model",))
    prompts = [[11, 22, 33, 44], [9, 8, 7], [301, 302, 303, 304, 305]]
    outs = {}
    for name, impl, m in (("sd_pallas", "pallas", None),
                          ("tp_xla", "xla", mesh),
                          ("tp_pallas", "pallas", mesh)):
        ecfg = EngineConfig(max_slots=4, max_len=128, max_output=64,
                            eos_id=-1, attn_impl=impl)
        with W.catch_warnings():
            W.simplefilter("error")  # any fallback warning fails the test
            eng = InferenceEngine(cfg, params, ecfg, mesh=m)
        if name == "tp_pallas":
            assert eng.pallas_fallback is False
            assert eng.pallas_fallback_reason is None
            assert eng.cfg.attn_impl == "pallas"
        jobs = [_mk(i, p) for i, p in enumerate(prompts)]
        t1, _ = eng.run_window(jobs[:2], 6)  # compacted decode
        for j, t in zip(jobs, t1):
            j.generated.extend(t)
        t2, _ = eng.run_window(jobs, 5)      # batched admission, full width
        outs[name] = (t1, t2)
    assert outs["tp_pallas"] == outs["tp_xla"], \
        f"{arch}: TP pallas diverged from TP xla"
    assert outs["tp_pallas"] == outs["sd_pallas"], \
        f"{arch}: TP pallas diverged from single-device pallas"


@needs8
def test_pallas_falls_back_with_reason_tp4_indivisible_kv():
    """qwen2-1.5b reduced has n_kv_heads=2: TP=4 cannot split the KV head
    axis (engine_shardings replicates KV), so the per-shard kernel would
    read the wrong local KV head — pallas must fall back, loudly, ONCE,
    and record a 'layout:' reason."""
    import warnings as W

    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((4,), ("model",))
    ecfg = EngineConfig(max_slots=2, max_len=64, max_output=16, eos_id=-1,
                        attn_impl="pallas")
    with W.catch_warnings(record=True) as rec:
        W.simplefilter("always")
        eng = InferenceEngine(cfg, params, ecfg, mesh=mesh)
        job = _mk(0, [5, 6, 7])
        eng.run_window([job], 4)
        eng.run_window([job], 4)
    assert eng.pallas_fallback
    assert eng.cfg.attn_impl == "xla"
    assert eng.pallas_fallback_reason.startswith("layout:")
    pallas_warns = [w for w in rec if "pallas" in str(w.message)]
    # the dedupe bugfix: once per ENGINE, not once per dispatch
    assert len(pallas_warns) == 1
    assert "layout:" in str(pallas_warns[0].message)
    # the fallback engine still serves: tokens match the unsharded ref
    ref = InferenceEngine(cfg, params, ecfg)
    rj = _mk(0, [5, 6, 7])
    r1, _ = ref.run_window([rj], 4)
    rj.generated.extend(r1[0])
    r2, _ = ref.run_window([rj], 4)
    assert eng.pallas_fallback  # unchanged by serving


@needs2
def test_pallas_fallback_reason_family_ssm():
    """ssm decode is a recurrent step with no attention read — under a
    mesh pallas falls back with a 'family:' reason (and off-mesh stays
    pallas, where it only affects prefill's ssd_scan)."""
    cfg = get_config("mamba2-130m").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((2,), ("model",))
    with pytest.warns(UserWarning, match="family:"):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_slots=2, max_len=64, attn_impl="pallas"),
            mesh=mesh)
    assert eng.pallas_fallback
    assert eng.pallas_fallback_reason.startswith("family:")
    # off-mesh, pallas stays pallas — no warning, no rewrite
    cfg_d = get_config("qwen2-1.5b").reduced()
    params_d = init_params(jax.random.PRNGKey(0), cfg_d)
    eng1 = InferenceEngine(
        cfg_d, params_d, EngineConfig(max_slots=2, max_len=64,
                                      attn_impl="pallas"))
    assert not eng1.pallas_fallback
    assert eng1.pallas_fallback_reason is None
    assert eng1.cfg.attn_impl == "pallas"


@needs2
def test_pallas_support_matrix():
    """pallas_decode_support's reason categories, directly."""
    from repro.launch.partition import pallas_decode_support

    dense = get_config("qwen2-1.5b").reduced()
    tp2 = make_mesh((2,), ("model",))
    assert pallas_decode_support(dense, tp2) is None
    r = pallas_decode_support(dense, fake_mesh((2,), ("data",)))
    assert r.startswith("mesh:")
    r = pallas_decode_support(get_config("mamba2-130m").reduced(), tp2)
    assert r.startswith("family:")
    r = pallas_decode_support(dense, fake_mesh((4,), ("model",)))
    assert r.startswith("layout:")


@needs2
def test_chunked_prefill_identity_under_tp2_pallas():
    """Chunked prefill + TP2 + pallas decode: same greedy tokens as the
    unsharded one-shot XLA engine (chunk attention is always sdpa; the
    pallas kernel serves the decode windows between chunks)."""
    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((2,), ("model",))
    ref = InferenceEngine(cfg, params,
                          EngineConfig(max_slots=2, max_len=128,
                                       max_output=64, eos_id=-1))
    sharded = InferenceEngine(
        cfg, params,
        EngineConfig(max_slots=2, max_len=128, max_output=64, eos_id=-1,
                     attn_impl="pallas"), mesh=mesh)
    assert sharded.pallas_fallback is False
    prompt = [11 + k % 60 for k in range(23)]
    out = {}
    for name, eng, chunk in (("ref", ref, None), ("tp2p", sharded, 6)):
        job = _mk(0, prompt)
        toks = []
        for _ in range(16):
            t, _ = eng.run_window([job], 4, prefill_chunk=chunk)
            job.generated.extend(t[0])
            toks.extend(t[0])
            if len(toks) >= 8:
                break
        out[name] = toks[:8]
    assert out["ref"] == out["tp2p"], \
        "chunked prefill under TP pallas diverged"


@needs2
def test_preempt_resume_identical_under_tp2_pallas():
    """Evict + recompute-resume on a TP2 pallas engine matches the
    unsharded XLA reference token-for-token."""
    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((2,), ("model",))
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(max_slots=1, max_len=128, max_output=64, eos_id=-1,
                     attn_impl="pallas"), mesh=mesh)
    assert eng.pallas_fallback is False
    ref = InferenceEngine(cfg, params,
                          EngineConfig(max_slots=1, max_len=128,
                                       max_output=64, eos_id=-1))
    out = {}
    for name, e in (("ref", ref), ("tp2p", eng)):
        job = _mk(0, [5, 6, 7])
        t1, _ = e.run_window([job], 5)
        job.generated.extend(t1[0])
        e.evict_job(job.job_id)
        t2, _ = e.run_window([job], 5)   # recompute-resume
        out[name] = t1[0] + t2[0]
    assert out["ref"] == out["tp2p"]


@needs2
def test_shard_map_kernel_matches_single_device_over_len_vectors():
    """Property test on the kernel wrapper itself: for random Q/K/V and
    per-slot kv_len vectors spanning the occupancy range (fresh slot,
    mid-stream, full buffer), the shard_map'd flash_decode is BITWISE
    identical to the single-device kernel."""
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    mesh = make_mesh((2,), ("model",))
    b, h, kh, d, L = 4, 4, 2, 16, 128
    rng = np.random.default_rng(0)
    len_vectors = [
        [1, 1, 1, 1],                 # every slot fresh
        [1, 37, 77, 128],             # mixed occupancy incl. full buffer
        [128, 128, 128, 128],         # all full
        [5, 5, 64, 3],                # duplicates + short
    ]
    for case, lens in enumerate(len_vectors):
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, L, kh, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, L, kh, d)), jnp.float32)
        kv_len = jnp.asarray(lens, jnp.int32)
        q_off = kv_len - 1
        ref = kops.flash_decode(q, k, v, kv_len=kv_len, q_offset=q_off)
        got = kops.flash_decode(q, k, v, kv_len=kv_len, q_offset=q_off,
                                mesh=mesh)
        assert np.array_equal(np.asarray(ref), np.asarray(got)), \
            f"case {case}: sharded kernel diverged from single-device"
        # the stacked (n_layers, B, L, KH, D) cache, read at a layer index
        ks, vs = jnp.stack([v, k]), jnp.stack([k, v])
        got = kops.flash_decode(q, ks, vs, kv_len=kv_len, q_offset=q_off,
                                mesh=mesh, layer=1)
        assert np.array_equal(np.asarray(ref), np.asarray(got)), \
            f"case {case}: sharded stacked read diverged from single-device"
    # indivisible heads must be rejected at the kernel boundary too
    k3 = jnp.zeros((b, L, 3, d), jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        kops.flash_decode(q, k3, k3, kv_len=jnp.ones((b,), jnp.int32),
                          q_offset=jnp.zeros((b,), jnp.int32), mesh=mesh)


@needs8
def test_make_tp_pods_disjoint_and_identical():
    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_slots=2, max_len=64, max_output=16, eos_id=-1)
    pods = make_tp_pods(cfg, params, ecfg, n_pods=2, tp=2)
    assert sorted(pods) == [0, 1]
    d0 = {d.id for d in np.asarray(pods[0].mesh.devices).ravel()}
    d1 = {d.id for d in np.asarray(pods[1].mesh.devices).ravel()}
    assert d0 and d1 and not d0 & d1
    # data parallelism: both pods serve the same model — identical tokens
    t0, _ = pods[0].run_window([_mk(0, [11, 22, 33])], 6)
    t1, _ = pods[1].run_window([_mk(0, [11, 22, 33])], 6)
    assert t0 == t1
    # over-ask relative to however many devices this process actually has
    # (the full test suite may run with dryrun's 512 forced host devices)
    too_many = len(jax.devices()) // 2 + 1
    with pytest.raises(RuntimeError, match="devices"):
        make_tp_pods(cfg, params, ecfg, n_pods=too_many, tp=2)


@needs2
def test_make_tp_pods_tp1_one_device_per_pod():
    """One-device replicas: pod n commits its params and slot cache to
    device n, stays there through serving, and decodes the same tokens as
    an unplaced engine."""
    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_slots=2, max_len=64, max_output=16, eos_id=-1)
    devices = jax.devices()
    pods = make_tp_pods(cfg, params, ecfg, n_pods=2, tp=1)
    ref, _ = InferenceEngine(cfg, params, ecfg).run_window(
        [_mk(0, [11, 22, 33])], 6)
    for n, eng in pods.items():
        assert eng.mesh is None
        logits = eng.prefill_logits([11, 22, 33])
        assert int(np.argmax(logits)) == ref[0][0]  # the first emission
        toks, _ = eng.run_window([_mk(0, [11, 22, 33])], 6)
        assert toks == ref
        leaves = jax.tree_util.tree_leaves((eng.params, eng.cache))
        assert {d for leaf in leaves for d in leaf.devices()} == {devices[n]}
    with pytest.raises(RuntimeError, match="devices"):
        make_tp_pods(cfg, params, ecfg, n_pods=len(devices) + 1, tp=1)


# --------------------------------------------------------------------------- #
# Per-node executor surface (runs on one device)
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def two_node_executor():
    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_slots=2, max_len=64, max_output=64, eos_id=-1)
    ex = EngineExecutor({0: InferenceEngine(cfg, params, ecfg),
                         1: InferenceEngine(cfg, params, ecfg)})
    jid = [0]

    def go(node, batch, window):
        jobs = [_mk(1000 + jid[0] + i, [3, 4, 5]) for i in range(batch)]
        jid[0] += batch
        ex.execute(node, jobs, window, now=0.0)
        for j in jobs:
            ex.evict(node, j)

    # node 0 sees more traffic than node 1, at two (batch, window) shapes
    for _ in range(3):
        go(0, 1, 2)
        go(0, 2, 4)
    go(1, 1, 2)
    go(1, 1, 4)
    return ex


def test_node_counters_separable(two_node_executor):
    ex = two_node_executor
    per = ex.node_counters()
    assert sorted(per) == [0, 1]
    assert per[0]["windows_executed"] == 6
    assert per[1]["windows_executed"] == 2
    # a storm on one pod is attributable: node 0 compiled two decode
    # shapes, node 1 two of its own
    for n in (0, 1):
        assert per[n]["decode_traces"] >= 1
        assert per[n]["decode_dispatches"] == per[n]["windows_executed"]
    agg = ex.counters()
    for k in ("prefill_traces", "prefill_dispatches", "decode_traces",
              "decode_dispatches", "windows_executed"):
        assert agg[k] == per[0][k] + per[1][k], k


def test_per_node_calibrated_profiles(two_node_executor):
    ex = two_node_executor
    profs = ex.calibrated_node_profiles()
    assert sorted(profs) == [0, 1]
    for n, p in profs.items():
        assert p.name == f"live-node{n}"
        assert p.decode_ms_1 > 0
    assert sorted(ex.node_fit_overhead_s) == [0, 1]
    costs = ex.node_token_cost()
    assert all(c > 0 for c in costs.values())
    # node filtering really filters: fitting node 0 alone must equal the
    # profile from a log containing only node-0 windows
    only0 = EngineExecutor(ex.engines)
    only0.window_log = [r for r in ex.window_log if r["node"] == 0]
    a = ex.calibrated_profile(nodes=[0])
    b = only0.calibrated_profile()
    assert np.isclose(a.avg_latency_ms, b.avg_latency_ms)
    assert np.isclose(a.batch_slowdown, b.batch_slowdown)
