"""Multi-head latent attention and YaRN (models/mla.py, models/rope.py):
the absorbed form that reads the latent cache against the expanded form
over the whole sequence, the latent cache's layout, the YaRN frequencies
against a numpy transcription of DeepSeek-V3's published formula, and the
model's parameter counts at the published widths."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_config
from repro.models import attention as attn
from repro.models import kv_cache as kvc
from repro.models import mla
from repro.models.rope import rope_cos_sin, yarn_freqs, yarn_mscale
from repro.models.transformer import ParallelCtx, _sp_decode_attention

R1 = get_config("deepseek-r1")


def _f32_cfg():
    """The reduced deepseek-r1 in float32, so the two forms differ only
    by the order of float32 sums."""
    return get_config("deepseek-r1").reduced().replace(dtype="float32")


def _yarn_numpy(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepSeek-V3 inference/model.py ``precompute_freqs_cis``, in numpy."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freqs = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    smooth = 1 - np.clip(linear, 0, 1)
    return freqs / factor * (1 - smooth) + freqs * smooth, (low, high)


def test_yarn_matches_the_published_formula():
    y = R1.rope_scaling
    want, (low, high) = _yarn_numpy(R1.mla.qk_rope_head_dim, R1.rope_theta,
                                    y.factor, y.original_max_position,
                                    y.beta_fast, y.beta_slow)
    assert (low, high) == (10, 23)
    got = np.asarray(yarn_freqs(R1.mla.qk_rope_head_dim, R1.rope_theta, y))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the high frequencies keep theta^(-2i/64), the low ones divide by 40
    plain = 1.0 / R1.rope_theta ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    # mscale == mscale_all_dim: the tables keep unit scale
    cos, sin = rope_cos_sin(jnp.arange(5), 64, R1.rope_theta, y)
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), 1.0,
                               rtol=1e-6)


def test_softmax_scale_carries_mscale_squared():
    want = 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2
    assert mla.softmax_scale(R1) == pytest.approx(want, rel=1e-12)
    assert yarn_mscale(40.0, 1.0) == pytest.approx(0.1 * math.log(40) + 1)


@pytest.fixture(scope="module")
def layer():
    cfg = _f32_cfg()
    p = attn.init_attention(jax.random.PRNGKey(0), cfg)
    # norms away from 1, so a norm that is skipped shows
    p = dict(p, q_norm=p["q_norm"] * 1.3, kv_norm=p["kv_norm"] * 0.7)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    return cfg, p, x


def test_absorbed_decode_matches_expanded_attention(layer):
    """Prefill 16 positions, then decode 8 through the latent cache
    (absorbed); the expanded form over all 24 agrees at each."""
    cfg, p, x = layer
    full = attn.full_attention(p, cfg, x, jnp.arange(24))
    _, (c_kv, k_pe) = attn.full_attention(p, cfg, x[:, :16], jnp.arange(16),
                                          return_kv=True)
    cache = kvc.write_prefill(
        kvc.init_kv_cache(2, 32, 1, cfg.mla.kv_lora_rank, jnp.float32,
                          v_head_dim=cfg.mla.qk_rope_head_dim), c_kv, k_pe)
    for t in range(16, 24):
        y, cache = attn.decode_attention(p, cfg, x[:, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(y[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=1e-4, atol=1e-5)
    assert int(cache.length[0]) == 24


def test_chunks_match_expanded_attention_dense_and_paged(layer):
    cfg, p, x = layer
    full = attn.full_attention(p, cfg, x, jnp.arange(24))
    r, rope = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    dense = kvc.init_kv_cache(2, 32, 1, r, jnp.float32, v_head_dim=rope)
    paged = kvc.init_paged_kv_cache(9, 8, 2, 4, 1, r, jnp.float32,
                                    v_head_dim=rope)
    paged = dataclasses.replace(paged, block_tables=jnp.array(
        [[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32))
    for cache in (dense, paged):
        outs = []
        for s in range(0, 24, 8):
            y, cache = attn.chunk_attention(p, cfg, x[:, s:s + 8], cache,
                                            s + jnp.arange(8))
            outs.append(y)
        np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                                   np.asarray(full), rtol=1e-4, atol=1e-5)


def test_latent_cache_layout():
    """One head of the normalised latent in k, one of the rotary key in
    v: 576 values a token and layer at the published widths."""
    from repro.models.transformer import build_model

    cfg = get_config("deepseek-r1-chip")
    model = build_model(cfg, num_servers=4)
    cache = model.init_cache(32, 2048, abstract=True)
    for part, layers in (("dense", 3), ("blocks", 4)):
        c = cache[part]
        assert c.k.shape == (layers, 32, 2048, 1, 512)
        assert c.v.shape == (layers, 32, 2048, 1, 64)
    total = sum(a.size * a.dtype.itemsize for part in cache.values()
                for a in (part.k, part.v))
    assert total == 7 * 32 * 2048 * 1152                   # 0.53 GB
    paged = model.init_paged_cache(65, 16, 32, 2048, abstract=True)
    assert paged["blocks"].v.shape == (4, 65, 16, 1, 64)


def test_mla_work_is_tagged_mla():
    cfg = _f32_cfg()
    p = attn.init_attention(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((1, 1, cfg.d_model))
    cache = kvc.init_kv_cache(1, 8, 1, cfg.mla.kv_lora_rank, jnp.float32,
                              v_head_dim=cfg.mla.qk_rope_head_dim)
    text = jax.jit(lambda p, x, c: attn.decode_attention(p, cfg, x, c)
                   ).lower(p, x, cache).compile().as_text()
    assert f'{tracing.STAGE_KEY}="mla"' in text
    assert f'{tracing.STAGE_KEY}="attention"' not in text


def test_sequence_sharded_decode_refuses_mla():
    cfg = _f32_cfg()
    with pytest.raises(ValueError, match="latent attention"):
        _sp_decode_attention({}, cfg, None, None,
                             ParallelCtx(seq_shard_cache=True))


def test_dry_run_decode_cell_takes_mla():
    """The dry run slot-shards a GQA cache for decode; a latent-attention
    model decodes there on the plain path instead of being refused."""
    from jax.sharding import Mesh

    from repro.configs.shapes import InputShape
    from repro.launch import dryrun

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    fn, args = dryrun.build_cell("deepseek-r1",
                                 InputShape("decode_tiny", 64, 2, "decode"),
                                 mesh, cfg=get_config("deepseek-r1").reduced())
    text = fn.lower(*args).as_text()
    assert "mla" in text


def test_parameter_counts_at_published_widths():
    """MLA's five projections and the dense width 18432: 671.0B in all
    (the published 671B excludes the MTP module), 37.6B active."""
    m = R1.mla
    per_layer = (7168 * 1536 + 1536 + 1536 * 128 * 192 + 7168 * 576 + 512
                 + 512 * 128 * 256 + 128 * 128 * 7168)
    assert m.num_params(7168, 128) == per_layer
    assert per_layer * 2 == pytest.approx(374e6, rel=0.01)    # bf16
    assert R1.num_params() / 1e9 == pytest.approx(671.0, abs=0.1)
    assert R1.num_active_params() / 1e9 == pytest.approx(37.6, abs=0.1)
    chip = get_config("deepseek-r1-chip")
    assert chip.num_params() * 2 / 1e9 == pytest.approx(11.5, abs=0.1)
