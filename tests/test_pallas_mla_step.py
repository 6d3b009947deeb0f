"""The one-pass absorbed step of latent attention
(ops/pallas_mla_step.py) against the plain form it stands in for on the
TPU (models/kimi_vl.py::latent_attention).

The suite runs on the CPU mesh, so every call passes ``interpret=True``:
the interpreter is never picked from the backend. The kernel compiled
for the chip at the timed shape is ``tests/test_tpu_hlo.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS
from actor_critic_algs_on_tensorflow_tpu.models import kimi_vl as kv
from actor_critic_algs_on_tensorflow_tpu.ops import pallas_mla_step

H, RANK, ROPE = 4, 128, 64
LAYERS, LAYER = 3, 1
SCALE = 0.3


def _inputs(B, L, dtype=jnp.float32, seed=0):
    """A query and three layers' caches; the middle layer is read, its
    neighbours hold NaN, which no index may reach."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(ks[0], (B, H, RANK + ROPE))
    cache = jax.random.normal(ks[1], (B, L, RANK + ROPE)).astype(dtype)
    return q, cache


def _stacked(cache):
    B, L, width = cache.shape
    return jnp.full((B, LAYERS, L, width), jnp.nan, cache.dtype).at[
        :, LAYER
    ].set(cache)


def _beyond(cache, pos, fill):
    """``cache`` with every row beyond ``pos`` holding ``fill``."""
    rows = jnp.arange(cache.shape[1])[None, :, None]
    return jnp.where(rows <= pos[:, None, None], cache,
                     jnp.asarray(fill, cache.dtype))


def _kernel(q, cache, pos, block_envs, chunk):
    return pallas_mla_step.latent_attention(
        q, _stacked(cache), LAYER, pos, scale=SCALE, rank=RANK,
        block_envs=block_envs, chunk=chunk, interpret=True,
    )


def _positions(B, L, chunk):
    """``pos [B]`` rows to try: the ends, on and beside every chunk
    boundary, and mixtures that differ within one block of envs."""
    edges = sorted({0, L - 1} | {
        p for c in range(chunk, L, chunk) for p in (c - 1, c, c + 1)
    })
    rows = [jnp.full((B,), p, jnp.int32) for p in edges]
    cycle = jnp.asarray(edges, jnp.int32)
    rows.append(jnp.resize(cycle, (B,)))
    rows.append(jnp.resize(cycle[::-1], (B,)))
    rows.append((jnp.arange(B, dtype=jnp.int32) * 7 + 3) % L)
    return rows


@pytest.mark.parametrize("B,L,block_envs,chunk", [
    # one block of envs, two chunks
    (4, 16, 4, 8),
    # blocks of 2 walked in groups, four chunks
    (4, 32, 2, 8),
    # a batch that is no multiple of the block: 6 envs, blocks of 4 asked
    (6, 32, 4, 16),
    # more envs a block than the body's group: a loop of two passes
    (16, 16, 16, 8),
    # the kernel's own chunk, and an odd batch (blocks of one env)
    (3, 256, 2, 128),
    # the whole cache one chunk: the exact softmax, nothing to skip
    (4, 16, 2, 16),
])
def test_kernel_equals_the_plain_core(B, L, block_envs, chunk):
    q, cache = _inputs(B, L)
    for pos in _positions(B, L, chunk):
        want = kv.latent_attention(q, cache, pos, SCALE, RANK, jnp.float32)
        got = _kernel(q, cache, pos, block_envs, chunk)
        assert got.shape == (B, H, RANK) and got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=str(pos))
    # and it attends: at the last position it is no row of the cache
    assert float(jnp.max(jnp.abs(got - cache[:, :1, :RANK]))) > 0.1


@pytest.mark.parametrize("fill", [float("nan"), float("inf"), 1e30])
def test_rows_beyond_the_position_come_out_unread(fill):
    """An unwritten row may hold anything: not even times zero does it
    reach the result, whatever ``pos [B]``, different within a block."""
    B, L, chunk = 8, 32, 8
    q, cache = _inputs(B, L, seed=1)
    pos = jnp.array([0, 7, 8, 9, 15, 16, 30, 31], jnp.int32)
    want = kv.latent_attention(q, cache, pos, SCALE, RANK, jnp.float32)
    got = _kernel(q, _beyond(cache, pos, fill), pos, 4, chunk)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_bfloat16_products_round_as_the_plain_form_does():
    """The cache's dtype into both products, float32 sums: against the
    plain form at the same precision the kernel differs by where one
    rounding of the probabilities sits, far below what the products'
    own rounding moves either of them from float32."""
    B, L = 6, 64
    q, cache = _inputs(B, L, jnp.bfloat16, seed=2)
    pos = jnp.array([0, 15, 16, 33, 62, 63], jnp.int32)
    exact = kv.latent_attention(
        q.astype(jnp.bfloat16), cache, pos, SCALE, RANK, jnp.float32
    )
    plain = kv.latent_attention(q, cache, pos, SCALE, RANK, jnp.bfloat16)
    got = _kernel(q, _beyond(cache, pos, jnp.nan), pos, 2, 16)
    err_kernel = float(jnp.max(jnp.abs(got - exact)))
    err_plain = float(jnp.max(jnp.abs(plain - exact)))
    assert err_kernel < 2 * err_plain + 1e-6, (err_kernel, err_plain)
    np.testing.assert_allclose(got, plain, atol=2e-2)


def test_a_position_past_the_cache_reads_every_row():
    """``pos >= L`` (a rollout longer than the cache would reach it):
    the plain form's mask shows all ``L`` rows, and so does the
    kernel."""
    B, L = 2, 16
    q, cache = _inputs(B, L, seed=3)
    pos = jnp.array([L, L + 5], jnp.int32)
    want = kv.latent_attention(q, cache, pos, SCALE, RANK, jnp.float32)
    np.testing.assert_allclose(_kernel(q, cache, pos, 2, 8), want, atol=2e-5)


@pytest.mark.parametrize("pos,block_envs,chunk,share", [
    # lock-step: chunks up to the one that holds pos
    ([0, 0, 0, 0], 2, 8, 1 / 4), ([8, 8, 8, 8], 2, 8, 2 / 4),
    ([31, 31, 31, 31], 2, 8, 1.0),
    # a block fetches what its furthest env needs: (2 + 4) / 2 chunks of 4
    ([0, 9, 3, 31], 2, 8, 3 / 4),
    # past the cache: every row, no more
    ([40, 40, 40, 40], 4, 8, 1.0),
])
def test_rows_read_share_counts_what_the_index_map_fetches(
        pos, block_envs, chunk, share):
    got = pallas_mla_step.rows_read_share(
        jnp.asarray(pos, jnp.int32), 32, block_envs=block_envs, chunk=chunk
    )
    assert float(got) == pytest.approx(share)


def test_the_kernel_refuses_a_gradient():
    """The step form is never differentiated by a trainer: asking is a
    mistake, and says where to go instead."""
    q, cache = _inputs(2, 16)
    pos = jnp.array([3, 9], jnp.int32)

    def loss(q):
        return jnp.sum(_kernel(q, cache, pos, 2, 8))

    with pytest.raises(NotImplementedError, match="mla_seq"):
        jax.grad(loss)(q)


def test_only_the_published_widths_take_the_kernel():
    assert pallas_mla_step.fits(jnp.zeros((1, 6, 512, 576)), 512)
    # rows that are not whole chunks, a latent part that is not whole
    # lane tiles, a cache with no rope part
    assert not pallas_mla_step.fits(jnp.zeros((1, 6, 500, 576)), 512)
    assert not pallas_mla_step.fits(jnp.zeros((1, 6, 512, 160)), 96)
    assert not pallas_mla_step.fits(jnp.zeros((1, 6, 512, 512)), 512)
    # the test preset's narrow cache goes to the plain form, which says
    # that it read every row
    tiny = PRESETS["ppo-kimivl-tiny"][1]
    cfg = tiny["seq_model"]
    caches = jnp.zeros((2, cfg.num_hidden_layers, tiny["rollout_length"],
                        cfg.cache_width))
    assert not pallas_mla_step.fits(caches, cfg.kv_lora_rank)
    q = jnp.ones((2, cfg.num_attention_heads, cfg.cache_width))
    pos = jnp.zeros((2,), jnp.int32)
    plain = kv._latent_attention(
        q, caches, 1, pos, 1.0, cfg.kv_lora_rank, jnp.float32
    )
    assert plain.shape == (2, cfg.num_attention_heads, cfg.kv_lora_rank)
    assert float(kv._rows_read_share(caches, pos, cfg.kv_lora_rank)) == 1.0
    with pytest.raises(ValueError, match="whole chunks"):
        pallas_mla_step.latent_attention(
            q, caches, 1, pos, scale=1.0, rank=cfg.kv_lora_rank, chunk=7,
            interpret=True,
        )
