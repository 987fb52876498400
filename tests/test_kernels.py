"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
from repro.kernels.flash_attention.kernel import (
    flash_attention_pallas, flash_attention_positions_pallas)
from repro.kernels.flash_attention.ref import (flash_attention_positions_ref,
                                               flash_attention_ref)
from repro.kernels.swarm_uncertainty.kernel import uncertainty_pallas
from repro.kernels.swarm_uncertainty.ref import uncertainty_ref

KEYS = jax.random.split(jax.random.PRNGKey(0), 8)


class TestSwarmUncertainty:
    @pytest.mark.parametrize("B,N,V,bn,bv,k", [
        (2, 16, 4096, 8, 1024, 10),
        (1, 8, 512, 8, 128, 5),
        (3, 32, 8192, 8, 2048, 16),
        (1, 8, 1024, 4, 256, 1),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, N, V, bn, bv, k, dtype):
        logits = (jax.random.normal(KEYS[0], (B, N, V), jnp.float32) * 3
                  ).astype(dtype)
        toks = jax.random.randint(KEYS[1], (B, N), 0, V)
        h, v, hd = uncertainty_pallas(logits, toks, k=k, bn=bn, bv=bv,
                                      interpret=True)
        hr, vr, hdr = uncertainty_ref(logits, toks, k=k)
        tol = 1e-4 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(h, hr, rtol=tol, atol=tol)
        np.testing.assert_allclose(v, vr, rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(hd, hdr, rtol=tol, atol=tol)

    def test_extreme_logits_stable(self):
        logits = jnp.full((1, 8, 512), -1e4).at[..., 0].set(1e4)
        toks = jnp.zeros((1, 8), jnp.int32)
        h, v, hd = uncertainty_pallas(logits, toks, k=4, bv=128,
                                      interpret=True)
        assert np.isfinite(np.asarray(h)).all()
        assert np.isfinite(np.asarray(v)).all()


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,K,D,causal,window,bq,bk", [
        (2, 256, 4, 2, 64, True, None, 64, 64),
        (1, 128, 8, 8, 32, False, None, 64, 32),
        (2, 256, 6, 2, 64, True, 64, 64, 64),      # sliding window
        (1, 512, 4, 1, 128, True, None, 128, 128),  # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, S, H, K, D, causal, window, bq, bk, dtype):
        q = jax.random.normal(KEYS[2], (B, S, H, D), dtype)
        k = jax.random.normal(KEYS[3], (B, S, K, D), dtype)
        v = jax.random.normal(KEYS[4], (B, S, K, D), dtype)
        out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     bq=bq, bk=bk, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=causal, window=window)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_matches_model_attention(self):
        """Kernel == the model's chunked online-softmax path."""
        from repro.models.attention import chunked_attention
        B, S, H, K, D = 1, 128, 4, 2, 32
        q = jax.random.normal(KEYS[5], (B, S, H, D), jnp.float32)
        k = jax.random.normal(KEYS[6], (B, S, K, D), jnp.float32)
        v = jax.random.normal(KEYS[7], (B, S, K, D), jnp.float32)
        pos = jnp.arange(S)
        out_model = chunked_attention(q, k, v, q_positions=pos,
                                      kv_positions=pos, causal=True,
                                      window=None, q_block=32, kv_block=32)
        out_kernel = flash_attention_pallas(q, k, v, causal=True,
                                            bq=32, bk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out_model, np.float32),
                                   np.asarray(out_kernel, np.float32),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("window", [None, 8])
    def test_positions_mode_matches_ref_and_chunked(self, window):
        """Positions-mode kernel (span attends over a live cache, empty
        slots pos = -1) == positions ref == the model's chunked path."""
        from repro.models.attention import chunked_attention
        B, S, T, H, K, D = 2, 8, 32, 4, 2, 32
        q = jax.random.normal(KEYS[2], (B, S, H, D), jnp.float32)
        k = jax.random.normal(KEYS[3], (B, T, K, D), jnp.float32)
        v = jax.random.normal(KEYS[4], (B, T, K, D), jnp.float32)
        # continuation layout: span at positions 20..27, cache holds 0..19
        # plus the span's own slots, tail slots empty (-1)
        qpos = jnp.arange(20, 20 + S, dtype=jnp.int32)
        kvpos = jnp.where(jnp.arange(T) < 28, jnp.arange(T), -1)
        out = flash_attention_positions_pallas(
            q, k, v, q_positions=qpos, kv_positions=kvpos, causal=True,
            window=window, bq=4, bk=8, interpret=True)
        ref = flash_attention_positions_ref(
            q, k, v, q_positions=qpos, kv_positions=kvpos, causal=True,
            window=window)
        ch = chunked_attention(q, k, v, q_positions=qpos, kv_positions=kvpos,
                               causal=True, window=window, q_block=4,
                               kv_block=8)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ch, np.float32),
                                   rtol=2e-2, atol=2e-2)


class TestDecodeAttention:
    @pytest.mark.parametrize("B,T,K,G,D,window,bt", [
        (2, 512, 2, 4, 64, None, 128),
        (1, 256, 4, 1, 32, 64, 64),
        (3, 1024, 2, 2, 64, None, 256),
        (1, 128, 1, 8, 128, None, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, T, K, G, D, window, bt, dtype):
        q = jax.random.normal(KEYS[0], (B, K, G, D), dtype)
        k = jax.random.normal(KEYS[1], (B, T, K, D), dtype)
        v = jax.random.normal(KEYS[2], (B, T, K, D), dtype)
        idx = jax.random.randint(KEYS[3], (B,), T // 2, T)
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        pos = jnp.where(pos <= idx[:, None], pos, -1)
        out = decode_attention_pallas(q, k, v, pos, idx, window=window,
                                      bt=bt, interpret=True)
        ref = decode_attention_ref(q, k, v, pos, idx, window=window)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_empty_cache_slots_masked(self):
        B, T, K, G, D = 1, 64, 1, 2, 16
        q = jax.random.normal(KEYS[4], (B, K, G, D))
        k = jnp.full((B, T, K, D), 1e3)   # poison empty slots
        v = jnp.full((B, T, K, D), 1e3)
        k = k.at[:, :4].set(jax.random.normal(KEYS[5], (B, 4, K, D)))
        v = v.at[:, :4].set(jax.random.normal(KEYS[6], (B, 4, K, D)))
        pos = jnp.full((B, T), -1).at[:, :4].set(jnp.arange(4)[None])
        idx = jnp.array([3])
        out = decode_attention_pallas(q, k, v, pos, idx, bt=32,
                                      interpret=True)
        assert float(jnp.abs(out).max()) < 50.0  # poison never attended


class TestPagedDecodeKernel:
    """Block-table kernel: ring layouts, sentinel entries, delta overlay —
    all validated against the gathered-view reference."""

    B, K, G, D, N, L, nb = 2, 2, 2, 32, 16, 8, 4   # Tl = 32

    def _pool_state(self, *, window, p0, sentinel=False):
        """Pool filled linearly up to p0[b] tokens per row (ring slots for
        windowed layers: slot = pos % Tl, wrapped writes land BELOW the
        linear position)."""
        B, K, D, N, L, nb = self.B, self.K, self.D, self.N, self.L, self.nb
        Tl = nb * L
        q = jax.random.normal(KEYS[0], (B, K, self.G, D), jnp.float32)
        k_pool = jax.random.normal(KEYS[1], (N, L, K, D), jnp.float32)
        v_pool = jax.random.normal(KEYS[2], (N, L, K, D), jnp.float32)
        table = jax.random.permutation(KEYS[3], N)[:B * nb].reshape(
            B, nb).astype(jnp.int32)
        if sentinel:
            table = table.at[0, nb - 1].set(N + 7)
        pos_pool = np.full((N, L), -1, np.int32)
        for b in range(B):
            for p in range(int(p0[b])):          # later writes win (ring)
                sl = p % Tl if window is not None else p
                blk = int(table[b, sl // L])
                if blk < N:
                    pos_pool[blk, sl % L] = p
        return q, k_pool, v_pool, jnp.asarray(pos_pool), table

    def _delta(self, p0, t_now, steps=6):
        dk = jax.random.normal(KEYS[4], (self.B, steps, self.K, self.D),
                               jnp.float32)
        dv = jax.random.normal(KEYS[5], (self.B, steps, self.K, self.D),
                               jnp.float32)
        dpos = jnp.where(jnp.arange(steps)[None] <= t_now,
                         p0[:, None] + jnp.arange(steps)[None],
                         -1).astype(jnp.int32)
        return dk, dv, dpos

    @pytest.mark.parametrize("window", [None, 32])
    @pytest.mark.parametrize("sentinel", [False, True])
    def test_delta_overlay_matches_ref(self, window, sentinel):
        Tl = self.nb * self.L
        idx = jnp.array([Tl + 5 if window is not None else Tl - 2,
                         Tl // 2], jnp.int32)
        p0 = idx - 3
        q, k_pool, v_pool, pos_pool, table = self._pool_state(
            window=window, p0=p0, sentinel=sentinel)
        dk, dv, dpos = self._delta(p0, t_now=3)
        out = paged_decode_attention_pallas(
            q, k_pool, v_pool, pos_pool, table, idx, window=window,
            delta_k=dk, delta_v=dv, delta_pos=dpos, p0=p0, interpret=True)
        ref = paged_decode_attention_ref(
            q, k_pool, v_pool, pos_pool, table, idx, window=window,
            delta_k=dk, delta_v=dv, delta_pos=dpos, p0=p0)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-5, atol=2e-5)

    def test_ring_wrap_reads_below_linear_position(self):
        """Windowed ring: index past the view length, writes wrapped — the
        kernel must attend the wrapped slots (positions idx-window+1..idx),
        matching the gathered-view reference on the ring layout."""
        window = self.nb * self.L                # Tl == window ring
        idx = jnp.array([window + 10, window + 3], jnp.int32)
        p0 = idx - 2
        q, k_pool, v_pool, pos_pool, table = self._pool_state(
            window=window, p0=p0)
        dk, dv, dpos = self._delta(p0, t_now=2)
        out = paged_decode_attention_pallas(
            q, k_pool, v_pool, pos_pool, table, idx, window=window,
            delta_k=dk, delta_v=dv, delta_pos=dpos, p0=p0, interpret=True)
        ref = paged_decode_attention_ref(
            q, k_pool, v_pool, pos_pool, table, idx, window=window,
            delta_k=dk, delta_v=dv, delta_pos=dpos, p0=p0)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-5, atol=2e-5)

    def test_sentinel_entries_never_attended(self):
        """Invalid table entries (>= N, empty serve slots) are masked out
        wholesale — poison in the clamped-to block never leaks."""
        Tl = self.nb * self.L
        idx = jnp.array([Tl - 2, Tl // 2], jnp.int32)
        p0 = idx + 1                              # no dispatch writes yet
        q, k_pool, v_pool, pos_pool, table = self._pool_state(
            window=None, p0=p0)
        table = table.at[0, self.nb - 1].set(self.N + 3)
        # poison the block the sentinel clamps to (N - 1) with huge values
        # at valid-looking positions
        k_pool = k_pool.at[self.N - 1].set(1e3)
        v_pool = v_pool.at[self.N - 1].set(1e3)
        out = paged_decode_attention_pallas(
            q, k_pool, v_pool, pos_pool, table, idx, interpret=True)
        ref = paged_decode_attention_ref(
            q, k_pool, v_pool, pos_pool, table, idx)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-5, atol=2e-5)

    def test_gathered_view_equivalence(self):
        """The paged ref matches the monolithic ref on the hand-gathered
        linear view with the delta scattered in — the oracle chain the
        engine parity suite leans on.  (Allclose, not exact: the paged ref
        concatenates delta rows after the view, so softmax summation order
        differs from the in-place scatter.)"""
        Tl = self.nb * self.L
        idx = jnp.array([Tl - 2, Tl // 2], jnp.int32)
        p0 = idx - 3
        q, k_pool, v_pool, pos_pool, table = self._pool_state(
            window=None, p0=p0)
        dk, dv, dpos = self._delta(p0, t_now=3)
        ref = paged_decode_attention_ref(
            q, k_pool, v_pool, pos_pool, table, idx, window=None,
            delta_k=dk, delta_v=dv, delta_pos=dpos, p0=p0)
        # hand-gather the linear view, then scatter the written delta rows
        flat = table.reshape(-1)
        k = jnp.take(k_pool, flat, axis=0).reshape(self.B, Tl, self.K, self.D)
        v = jnp.take(v_pool, flat, axis=0).reshape(self.B, Tl, self.K, self.D)
        pos = jnp.take(pos_pool, flat, axis=0).reshape(self.B, Tl)
        b = jnp.arange(self.B)[:, None]
        sl = jnp.where(dpos >= 0, dpos, Tl)      # slot == position (linear)
        k = k.at[b, sl].set(dk, mode="drop")
        v = v.at[b, sl].set(dv, mode="drop")
        pos = pos.at[b, sl].set(dpos, mode="drop")
        mono = decode_attention_ref(q, k, v, pos, idx)
        np.testing.assert_allclose(np.asarray(ref, np.float32),
                                   np.asarray(mono, np.float32),
                                   rtol=1e-5, atol=1e-5)


class TestBlockSnapping:
    """Grid legality for geometries the old ``min(cap, dim)`` policy
    rejected (ISSUE 9): legal serving shapes whose dimension is not a
    multiple of the default block cap must snap to a dividing block and
    still match the reference — previously these tripped the kernels'
    divisibility asserts on TPU (e.g. a 640-slot cache vs bt=512,
    llama3's 128256-entry vocab vs bv=2048)."""

    def test_snap_block_properties(self):
        from repro.kernels.blocking import snap_block
        for dim in (64, 192, 320, 640, 1280, 49152, 128256, 152064, 202048):
            for cap in (8, 256, 512, 2048):
                b = snap_block(dim, cap, 1)
                assert 1 <= b <= min(cap, dim) and dim % b == 0, (dim, cap)
                for align in (8, 128):   # TPU tiles, else the whole dim
                    b = snap_block(dim, cap, align)
                    assert dim % b == 0 and (
                        b == dim or (b % align == 0 and b <= cap)), (
                            dim, cap, align, b)
        # the documented regressions: old policy was min(cap, dim)
        assert 640 % min(512, 640) != 0
        assert 128256 % min(2048, 128256) != 0

    def test_decode_attention_non_multiple_cache_len(self):
        # T=640 is a legal cache length (64-granule growth) with bt=512
        B, T, K, G, D = 2, 640, 2, 2, 32
        q = jax.random.normal(KEYS[0], (B, K, G, D), jnp.float32)
        k = jax.random.normal(KEYS[1], (B, T, K, D), jnp.float32)
        v = jax.random.normal(KEYS[2], (B, T, K, D), jnp.float32)
        idx = jnp.full((B,), T - 1)
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        out = decode_attention_pallas(q, k, v, pos, idx, interpret=True)
        ref = decode_attention_ref(q, k, v, pos, idx)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_flash_attention_non_multiple_lengths(self):
        # S=T=320 (a 64-granule length) vs the 256 default tiles
        B, S, H, K, D = 1, 320, 4, 2, 32
        q = jax.random.normal(KEYS[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(KEYS[1], (B, S, K, D), jnp.float32)
        v = jax.random.normal(KEYS[2], (B, S, K, D), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_uncertainty_non_multiple_vocab(self):
        # V=672 vs an explicit bv=256 cap: no multiple of 128 divides 672,
        # so the whole row is one lane tile
        B, N, V = 2, 8, 672
        logits = jax.random.normal(KEYS[0], (B, N, V), jnp.float32) * 3
        toks = jax.random.randint(KEYS[1], (B, N), 0, V)
        h, v, hd = uncertainty_pallas(logits, toks, k=5, bv=256,
                                      interpret=True)
        hr, vr, hdr = uncertainty_ref(logits, toks, k=5)
        np.testing.assert_allclose(h, hr, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(v, vr, rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(hd, hdr, rtol=1e-4, atol=1e-4)
