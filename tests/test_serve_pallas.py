"""Pallas fused paged-attention decode (kernels/paged_attention.py +
``attn_backend="pallas"``):

The flash-decoding kernel walks the page table directly — block-per-page
grid, online softmax across the page axis, pool indexed through a
scalar-prefetched BlockSpec index map — so contiguous per-row KV is
never materialized. It must be a drop-in for the gather backend: greedy
serve outputs token-identical on dense/MoE/enc-dec/prefix+lazy/tp2,
exactly ONE decode trace per page bucket (identical retrace cadence),
``kv_len = pos + 1`` masking null-page-0 / reservation tails / ragged
last pages, GQA q-heads folded to their kv head in-kernel. Kernel-level
parity runs against the gather reference on adversarial tables
(permuted, fragmented, null-padded). The HLO test pins the point of the
exercise: the gather backend's ``(B, P*page_size, Hkv, D)`` intermediate
is ABSENT from the pallas decode program.

Also hosts the non-hypothesis flash_attention regressions (ragged
lengths pad-and-mask, native-GQA forward/backward) — tests/test_kernels
is skipped wholesale when hypothesis is missing, these must not be.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.models import get_model, layers
from repro.serve.engine import ServeEngine
from repro.serve.parallel import ReplicaRouter, replica_meshes

CFG = ModelConfig(name="pal-dense", arch_type="dense", num_layers=2,
                  d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                  vocab_size=128, dtype="float32")

MOE_CFG = ModelConfig(name="pal-moe", arch_type="moe", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      num_experts=4, experts_per_token=2, vocab_size=128,
                      dtype="float32")

AUDIO_CFG = ModelConfig(name="pal-encdec", arch_type="audio",
                        num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=4, d_ff=128, vocab_size=128,
                        encoder_layers=1, encoder_ctx=12, dtype="float32")


def _params(cfg, seed=0):
    return get_model(cfg).init(jax.random.key(seed), cfg)


def _prompts(rng, cfg, lens):
    return [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in lens]


def _serve(cfg, params, prompts, new, *, frames=None, mesh=None, slots=2,
           max_len=64, **kw):
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len, mesh=mesh,
                      paged=True, **kw)
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new=new,
                   frames=None if frames is None else frames[i])
    results = eng.run()
    return {i: results[i].out for i in results}, eng


# ------------------------------------------------------- kernel parity

def _rand_paged(rng, *, b, width, n_pages, page_size, hq, hkv, d):
    """A head-major pool + adversarial tables: page ids permuted and
    fragmented (interleaved across rows, non-contiguous, nowhere in
    logical order), page 0 reserved as the null page, per-row cursors
    landing mid-page so the last page is ragged."""
    kp = jnp.asarray(rng.standard_normal((n_pages, hkv, page_size, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, hkv, page_size, d)),
                     jnp.float32)
    perm = rng.permutation(np.arange(1, n_pages))  # never the null page
    tab = np.zeros((b, width), np.int32)
    pos = np.zeros((b,), np.int32)
    k = 0
    for r in range(b):
        # ragged: row r holds r+1 pages, cursor inside the last one
        n_blk = r % width + 1
        tab[r, :n_blk] = perm[k:k + n_blk]
        k += n_blk
        pos[r] = (n_blk - 1) * page_size + int(rng.integers(0, page_size))
    return kp, vp, jnp.asarray(tab), jnp.asarray(pos)


def test_kernel_matches_gather_on_fragmented_tables():
    """GQA decode over permuted/fragmented tables with ragged last pages
    and null-page tails: the fused kernel matches the gather reference
    for every row."""
    rng = np.random.default_rng(0)
    kp, vp, tab, pos = _rand_paged(rng, b=4, width=4, n_pages=16,
                                   page_size=8, hq=8, hkv=2, d=32)
    q = jnp.asarray(rng.standard_normal((4, 1, 8, 32)), jnp.float32)
    want = layers.paged_attention(q, kp, vp, tab, pos, backend="gather")
    got = layers.paged_attention(q, kp, vp, tab, pos, backend="pallas")
    assert got.shape == want.shape == (4, 1, 8, 32)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_kernel_masks_null_pages_exactly():
    """Garbage in the pool behind null-page-0 table entries and past
    each cursor must not leak: poisoning page 0 and all unreferenced
    pages with huge values changes nothing."""
    rng = np.random.default_rng(1)
    kp, vp, tab, pos = _rand_paged(rng, b=3, width=4, n_pages=16,
                                   page_size=4, hq=4, hkv=4, d=16)
    q = jnp.asarray(rng.standard_normal((3, 1, 4, 16)), jnp.float32)
    clean = layers.paged_attention(q, kp, vp, tab, pos, backend="pallas")
    live = np.unique(np.asarray(tab))
    poison = np.setdiff1d(np.arange(16), live[live > 0])
    kp = kp.at[poison].set(1e9)
    vp = vp.at[poison].set(1e9)
    # ...and garbage INSIDE referenced pages past the cursor (ragged tail)
    for r in range(3):
        last = int(np.asarray(tab)[r, int(pos[r]) // 4])
        kp = kp.at[last, :, int(pos[r]) % 4 + 1:].set(1e9)
        vp = vp.at[last, :, int(pos[r]) % 4 + 1:].set(1e9)
    dirty = layers.paged_attention(q, kp, vp, tab, pos, backend="pallas")
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    want = layers.paged_attention(q, kp, vp, tab, pos, backend="gather")
    np.testing.assert_allclose(dirty, want, atol=2e-5, rtol=2e-5)


def test_kernel_hlo_has_no_gathered_kv():
    """The point of the kernel: the gather backend materializes a
    ``(B, P*page_size, Hkv, D)`` contiguous-KV intermediate per call;
    the pallas program must not."""
    b, width, page_size, hkv, d = 2, 4, 8, 2, 32
    kp = jnp.zeros((16, hkv, page_size, d), jnp.float32)
    q = jnp.zeros((b, 1, 2 * hkv, d), jnp.float32)
    tab = jnp.zeros((b, width), jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    gathered = f"f32[{b},{width * page_size},{hkv},{d}]"

    def run(backend):
        fn = lambda *a: layers.paged_attention(*a, backend=backend)
        return jax.jit(fn).lower(q, kp, kp, tab, pos) \
            .compile().as_text()
    assert gathered in run("gather")        # the baseline really does it
    assert gathered not in run("pallas")    # the kernel never does


# -------------------------------------------------------- serve parity

def test_pallas_dense_matches_gather():
    """Greedy dense serve is bit-identical across backends, one decode
    trace each, and the backend is observable in stats."""
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(0), CFG, (5, 7, 6, 8, 5))
    base, be = _serve(CFG, params, prompts, 6, attn_backend="gather")
    pal, pe = _serve(CFG, params, prompts, 6, attn_backend="pallas")
    assert pal == base
    assert be.stats["decode_traces"] == pe.stats["decode_traces"] == 1
    assert be.stats["decode_backend"] == "gather"
    assert pe.stats["decode_backend"] == "pallas"
    pe.reset_stats()
    assert pe.stats["decode_backend"] == "pallas"   # identity survives


def test_pallas_moe_matches_gather():
    params = _params(MOE_CFG, seed=5)
    prompts = _prompts(np.random.default_rng(6), MOE_CFG, (5, 8, 6))
    kw = dict(slots=1, max_len=32, page_size=8)
    base, _ = _serve(MOE_CFG, params, prompts, 4, **kw)
    pal, pe = _serve(MOE_CFG, params, prompts, 4, attn_backend="pallas",
                     **kw)
    assert pal == base
    assert pe.stats["decode_traces"] == 1


def test_pallas_encdec_matches_gather():
    """Enc-dec: the kernel runs on the paged self-attention KV while the
    per-slot cross-KV path is untouched."""
    params = _params(AUDIO_CFG, seed=2)
    rng = np.random.default_rng(2)
    prompts = _prompts(rng, AUDIO_CFG, (4, 7, 5))
    frames = [rng.standard_normal(
        (AUDIO_CFG.encoder_ctx, AUDIO_CFG.d_model)).astype(np.float32)
        for _ in prompts]
    base, _ = _serve(AUDIO_CFG, params, prompts, 5, frames=frames,
                     max_len=32)
    pal, pe = _serve(AUDIO_CFG, params, prompts, 5, frames=frames,
                     max_len=32, attn_backend="pallas")
    assert pal == base
    assert pe.stats["decode_traces"] == 1


def test_pallas_prefix_cache_lazy_matches_gather():
    """CoW sharing + lazy growth only rewrite table VALUES — the kernel
    is as layout-blind as the gather, with the same prefix hit counts."""
    params = _params(CFG)
    rng = np.random.default_rng(4)
    sys_p = rng.integers(0, CFG.vocab_size, size=(16,))
    prompts = [np.concatenate(
        [sys_p, rng.integers(0, CFG.vocab_size, size=(5,))]
    ).astype(np.int32) for _ in range(4)]
    kw = dict(prefix_cache=True, lazy=True)
    base, be = _serve(CFG, params, prompts, 6, **kw)
    pal, pe = _serve(CFG, params, prompts, 6, attn_backend="pallas", **kw)
    assert pal == base
    assert pe.stats["decode_traces"] == 1
    assert pe.stats["prefix_hit_blocks"] > 0
    assert pe.stats["prefix_hit_blocks"] == be.stats["prefix_hit_blocks"]


def test_pallas_tp2_matches_gather_tp1():
    """The kernel composes with the head-sharded pool: each shard's grid
    covers its own Hkv/tp heads, outputs stay bit-identical to the
    unsharded gather engine."""
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(3), CFG, (5, 7, 6, 8))
    base, be = _serve(CFG, params, prompts, 6)
    [mesh] = replica_meshes(1, 2)
    pal, pe = _serve(CFG, params, prompts, 6, mesh=mesh,
                     attn_backend="pallas")
    assert pal == base
    assert pe.tp == 2
    assert pe.stats["decode_traces"] == 1
    assert pe.per_device_kv_bytes() * 2 == be.per_device_kv_bytes()


def test_pallas_bucket_retrace_cadence_matches_gather():
    """Shapes depend only on the bucketed table width: the pallas
    program retraces exactly when the gather one does — when a LONGER
    request pushes the worst-case reservation over a power-of-two page
    bucket — and never mid-decode."""
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(9), CFG, (5, 6))

    def waves(backend):
        eng = ServeEngine(CFG, params, slots=1, max_len=64, paged=True,
                          page_size=4, attn_backend=backend)
        eng.submit(0, prompts[0], max_new=4)     # 9 tok -> bucket 4
        out = {0: eng.run()[0].out}
        first = eng.stats["decode_traces"]
        eng.submit(1, prompts[1], max_new=30)    # 36 tok -> bucket 16
        out[1] = eng.run()[1].out
        return out, first, eng.stats["decode_traces"]

    base, bfirst, btotal = waves("gather")
    pal, pfirst, ptotal = waves("pallas")
    assert pal == base
    assert (bfirst, btotal) == (pfirst, ptotal) == (1, 2)


def test_pallas_dp_router_aggregates_backend():
    """ReplicaRouter passes attn_backend through and its summed stats
    carry the identity field instead of crashing on the string."""
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(5), CFG, (5, 7, 6, 8))
    base, _ = _serve(CFG, params, prompts, 6)
    router = ReplicaRouter(CFG, params, dp=2, slots=2, max_len=64,
                           paged=True, attn_backend="pallas")
    for i, p in enumerate(prompts):
        router.submit(i, p, max_new=6)
    res = router.run()
    assert {i: res[i].out for i in res} == base
    st = router.stats
    assert st["decode_backend"] == "pallas"
    assert all(r["decode_traces"] == 1 for r in st["replicas"])


def test_attn_backend_validation():
    params = _params(CFG)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(CFG, params, slots=2, max_len=64, paged=False,
                    attn_backend="pallas")
    with pytest.raises(ValueError, match="gather"):
        ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                    attn_backend="triton")


# -------------------------------- flash_attention regressions (no
# hypothesis — tests/test_kernels is importorskip'd on it wholesale)

def test_flash_ragged_lengths_match_ref():
    """Sequence lengths that don't divide the block sizes used to trip a
    bare AssertionError; the wrapper now pads and masks, so any shape
    matches the dense reference."""
    rng = np.random.default_rng(0)
    for s, t in ((192, 192), (100, 150), (7, 130)):
        q = jnp.asarray(rng.standard_normal((2, s, 4, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, t, 4, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, t, 4, 64)), jnp.float32)
        causal = s == t
        out = ops.flash_attention(q, k, v, causal=causal)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        assert out.shape == (2, s, 4, 64)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_flash_gqa_native_forward_and_grads():
    """GQA runs without pre-repeating K/V: the kv row folds into the
    kernel's index map, and the backward group-sums dk/dv back to Hkv.
    Both must match autodiff through the repeated dense reference."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 128, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 2, 64)), jnp.float32)

    def loss(fn, rep):
        return lambda q, k, v: fn(
            q, jnp.repeat(k, rep, 2) if rep > 1 else k,
            jnp.repeat(v, rep, 2) if rep > 1 else v, causal=True).sum()

    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, jnp.repeat(k, 4, 2),
                                   jnp.repeat(v, 4, 2), causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    grads = jax.grad(loss(ops.flash_attention, 1), (0, 1, 2))(q, k, v)
    wants = jax.grad(loss(ref.flash_attention_ref, 4), (0, 1, 2))(q, k, v)
    for g, w in zip(grads, wants):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)

    with pytest.raises(ValueError, match="multiple"):
        ops.flash_attention(q[:, :, :5], k, v)   # 5 % 2 != 0
