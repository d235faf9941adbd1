"""The sharded system must compute the same numbers as one device:
train_step and decode under dp x tp (+SP, +FSDP) == unsharded reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import sharding as shd
from repro.core.pspec import sharding_rules
from repro.core.strategy import Strategy
from repro.models import get_model
from repro.train.step import init_opt_state, make_train_step
from repro.launch.mesh import make_mesh


def _mesh(data, model):
    return make_mesh((data, model), ("data", "model"))


@pytest.mark.parametrize("arch", ["qwen3-14b", "olmoe-1b-7b", "mamba2-780m"])
@pytest.mark.parametrize("strategy_kw", [
    dict(),                                  # Megatron baseline
    dict(seq_parallel=True),                 # +SP (Korthikanti)
    dict(fsdp=True),                         # +ZeRO-3
])
def test_train_step_sharded_equals_reference(arch, strategy_kw):
    cfg = get_smoke(arch).with_(dtype="float32", moe_capacity_factor=16.0)
    mod = get_model(cfg)
    key = jax.random.key(0)
    params = mod.init(key, cfg)
    st = Strategy(remat=False, microbatches=1, dtype="float32",
                  **strategy_kw)
    step = make_train_step(cfg, st, lr=1e-3)
    opt = init_opt_state(params, st)
    batch = {"tokens": jax.random.randint(key, (4, 64), 0, cfg.vocab_size)}

    p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch)

    mesh = _mesh(2, 4)
    with sharding_rules(mesh, st.rules(mesh)):
        psh = jax.tree.map(lambda s: jax.NamedSharding(mesh, s),
                           shd.param_pspecs(params, st, mesh))
        osh = jax.tree.map(lambda s: jax.NamedSharding(mesh, s),
                           shd.opt_state_pspecs(opt, params, st, mesh))
        p_sh, o_sh, m_sh = jax.jit(
            step, in_shardings=(psh, osh, None),
            out_shardings=(psh, osh, None))(params, opt, batch)

    assert m_sh["loss"] == pytest.approx(float(m_ref["loss"]), abs=1e-4)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("arch", ["qwen3-14b", "zamba2-1.2b"])
def test_decode_sharded_equals_reference(arch):
    cfg = get_smoke(arch).with_(dtype="float32")
    mod = get_model(cfg)
    key = jax.random.key(1)
    params = mod.init(key, cfg)
    b, s = 4, 32
    cache = mod.init_cache(cfg, b, s)
    tok = jax.random.randint(key, (b, 1), 0, cfg.vocab_size)
    pos = jnp.asarray(0, jnp.int32)

    def step(p, c, t):
        return mod.decode_step(p, c, t, pos, cfg)

    ref_logits, _ = jax.jit(step)(params, cache, tok)

    st = Strategy(remat=False, dtype="float32")
    mesh = _mesh(2, 4)
    with sharding_rules(mesh, st.rules(mesh)):
        psh = jax.tree.map(lambda sp: jax.NamedSharding(mesh, sp),
                           shd.param_pspecs(params, st, mesh))
        csh = jax.tree.map(lambda sp: jax.NamedSharding(mesh, sp),
                           shd.cache_pspecs(cache, st, mesh, b))
        sh_logits, _ = jax.jit(step, in_shardings=(psh, csh, None)
                               )(params, cache, tok)
    np.testing.assert_allclose(np.asarray(ref_logits),
                               np.asarray(sh_logits), atol=5e-4, rtol=5e-4)


def test_microbatch_invariance():
    """Grad accumulation over microbatches == single big batch."""
    cfg = get_smoke("minitron-4b").with_(dtype="float32")
    mod = get_model(cfg)
    key = jax.random.key(2)
    params = mod.init(key, cfg)
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}
    outs = {}
    for m in (1, 4):
        st = Strategy(remat=False, microbatches=m, dtype="float32")
        step = make_train_step(cfg, st, lr=1e-3)
        opt = init_opt_state(params, st)
        p2, _, met = jax.jit(step)(params, opt, batch)
        outs[m] = (p2, float(met["loss"]))
    assert outs[1][1] == pytest.approx(outs[4][1], abs=1e-5)
    for a, b in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
