"""The mixed step's paged attention runs over its live rows only
(layers.paged_attention's ``rows``):

  * the width ladder (``row_widths``/``row_width``);
  * ``rows=n`` equals the uncut call on rows ``[0, n)`` and gives zeros
    on ``[n, T)``, for both backends (pallas in interpret mode);
  * a mixed engine whose steps run several widths keeps ONE decode
    trace, emits the legacy split path's greedy tokens (dense and
    no-drop MoE), and counts each width's steps in ``engine.stats`` and
    on the tracer's step records.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import get_model, layers
from repro.serve.engine import ServeEngine

CFG = ModelConfig(name="rows-dense", arch_type="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=128, dtype="float32")

# capacity_factor = E / k: no token is ever dropped, so the mixed and
# split paths route identically (tests/test_serve_mixed.py)
MOE_CFG = ModelConfig(name="rows-moe", arch_type="moe", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      num_experts=4, experts_per_token=2,
                      moe_capacity_factor=2.0, vocab_size=128,
                      dtype="float32")

T = 24          # the ladder (8, 16, 24): a top width that is no power of two


def test_row_widths_ladder():
    assert layers.row_widths(256) == (8, 16, 32, 64, 128, 256)
    assert layers.row_widths(T) == (8, 16, 24)
    assert layers.row_widths(8) == (8,)
    assert layers.row_widths(4) == (4,)
    assert [layers.row_width(n, 256) for n in (0, 1, 8, 9, 24, 129, 256)] \
        == [8, 8, 8, 16, 32, 256, 256]
    assert [layers.row_width(n, T) for n in (1, 8, 9, 16, 17, 24)] == \
        [8, 8, 16, 16, 24, 24]


def _paged_inputs(seed=0, n_pages=24, width=4, page_size=4, hq=4, hkv=2,
                  d=16):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.standard_normal((n_pages, hkv, page_size, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, hkv, page_size, d)),
                     jnp.float32)
    tab = rng.integers(1, n_pages, size=(T, width)).astype(np.int32)
    pos = rng.integers(0, width * page_size, size=(T,)).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((T, 1, hq, d)), jnp.float32)
    return q, kp, vp, jnp.asarray(tab), jnp.asarray(pos)


@pytest.mark.parametrize("n", [1, 8, 9, T - 1, T])
@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_rows_cut_equals_full_call_on_live_rows(backend, n):
    q, kp, vp, tab, pos = _paged_inputs()
    full = layers.paged_attention(q, kp, vp, tab, pos, backend=backend)
    cut = jax.jit(lambda r, *a: layers.paged_attention(
        *a, backend=backend, rows=r))(jnp.int32(n), q, kp, vp, tab, pos)
    assert cut.shape == full.shape == (T, 1, 4, 16)
    np.testing.assert_allclose(np.asarray(cut[:n]), np.asarray(full[:n]),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(cut[n:]).any()


def test_rows_cut_is_one_switch_in_one_program():
    """One conditional with a branch per width, each gathering its
    width's ``(w, P*page_size, Hkv, D)`` K/V; the widest is the uncut
    gather's."""
    q, kp, vp, tab, pos = _paged_inputs()
    text = jax.jit(lambda r, *a: layers.paged_attention(*a, rows=r)).lower(
        jnp.int32(3), q, kp, vp, tab, pos).as_text()
    assert text.count("stablehlo.case") == 1
    for w in layers.row_widths(T):
        assert f"tensor<{w}x16x2x16xf32>" in text, w


def _params(cfg, seed=0):
    return get_model(cfg).init(jax.random.key(seed), cfg)


def _serve(cfg, params, prompts, new, *, mixed):
    # max_len 64 at page 16: the first request's worst-case reservation
    # (50 + 6 - 1 positions) books the top page bucket, so the mixed
    # program is traced once
    eng = ServeEngine(cfg, params, slots=4, max_len=64, paged=True,
                      mixed=mixed, **({"chunk_tokens": 32} if mixed else {}))
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new=new)
    res = eng.run()
    return {i: list(res[i].out) for i in res}, eng


@pytest.mark.parametrize("cfg", [CFG, MOE_CFG], ids=["dense", "moe"])
def test_mixed_steps_across_widths_match_split(cfg):
    params = _params(cfg, seed=3)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (50, 3, 40, 12, 9, 2)]
    split, _ = _serve(cfg, params, prompts, 6, mixed=False)
    mixed, eng = _serve(cfg, params, prompts, 6, mixed=True)
    assert mixed == split
    assert eng.stats["decode_traces"] == 1
    counts = {w: eng.stats[f"attn_steps_w{w}"]
              for w in layers.row_widths(32)}
    assert set(counts) == {8, 16, 32}
    assert all(counts.values()), counts          # every width ran
    # one count per launched program, as the step records say
    recs = [s for s in eng.tracer.flight()["steps"]
            if s["attn_rows"] is not None]
    assert sum(counts.values()) == len(recs)
    for w in counts:
        assert sum(s["attn_rows"] == w for s in recs) == counts[w]
    for s in recs:
        assert s["budget"] == 32
        assert s["attn_rows"] == layers.row_width(s["rows"], 32)
        live = sum(it.get("count", it.get("tokens", 0)) for it in s["work"])
        assert 0 < s["rows"] <= live


def test_mixed_program_switches_on_the_live_rows():
    """The engine's step program holds the width switch, and pads past
    the last live row are not attended: the engine's records show
    decode-only steps at the narrowest width."""
    params = _params(CFG)
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=True, chunk_tokens=32)
    eng.submit(0, np.arange(5, dtype=np.int32), max_new=4)
    eng.run()
    zeros = np.zeros(32, np.int32)
    text = eng._mixed.lower(
        eng.params, eng._cache, np.zeros((32, 1), np.int32), zeros, zeros,
        np.ones(32, bool), np.ones(32, bool), None).as_text()
    # one switch per layer body: the scan traces its body once
    assert len(re.findall(r"stablehlo\.case", text)) == 1
    recs = eng.tracer.flight()["steps"]
    assert [s["rows"] for s in recs] == [5, 1, 1, 1]
    assert [s["attn_rows"] for s in recs] == [8, 8, 8, 8]
    assert eng.stats["attn_steps_w8"] == 4
    eng.reset_stats()
    assert eng.stats["attn_steps_w8"] == 0


def test_level0_tracer_and_legacy_path_take_no_row_record():
    params = _params(CFG)
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=True, chunk_tokens=16, trace_level=0)
    eng.submit(0, np.arange(5, dtype=np.int32), max_new=3)
    eng.run()
    assert eng.stats["attn_steps_w8"] + eng.stats["attn_steps_w16"] == 3
    legacy = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                         mixed=False)
    legacy.submit(0, np.arange(5, dtype=np.int32), max_new=3)
    legacy.run()
    assert not any(k.startswith("attn_steps_") for k in legacy.stats)
    assert all(s["attn_rows"] is None
               for s in legacy.tracer.flight()["steps"])
