"""Pallas kernel correctness vs XLA reference compositions (interpret mode
on CPU; the same kernels compile natively on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


class TestLayerNormKernel:
    def test_matches_reference(self, rng):
        from paddle_tpu.kernels.layer_norm import layer_norm_pallas
        from paddle_tpu.ops.nn_functional import layer_norm

        x = rng.standard_normal((32, 256)).astype(np.float32)
        w = rng.standard_normal((256,)).astype(np.float32)
        b = rng.standard_normal((256,)).astype(np.float32)
        ref = layer_norm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                         1e-5, -1)
        got = layer_norm_pallas(jnp.asarray(x), jnp.asarray(w),
                                jnp.asarray(b), 1e-5, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_3d_input(self, rng):
        from paddle_tpu.kernels.layer_norm import layer_norm_pallas
        from paddle_tpu.ops.nn_functional import layer_norm

        x = rng.standard_normal((4, 16, 128)).astype(np.float32)
        w = np.ones((128,), np.float32)
        b = np.zeros((128,), np.float32)
        ref = layer_norm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                         1e-5, -1)
        got = layer_norm_pallas(jnp.asarray(x), jnp.asarray(w),
                                jnp.asarray(b), 1e-5, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


    def test_backward_matches_reference(self, rng):
        from paddle_tpu.kernels.layer_norm import layer_norm_pallas
        from paddle_tpu.ops.nn_functional import layer_norm

        x = rng.standard_normal((16, 128)).astype(np.float32)
        w = rng.standard_normal((128,)).astype(np.float32)
        b = rng.standard_normal((128,)).astype(np.float32)

        def loss_pallas(x_, w_, b_):
            return jnp.sum(layer_norm_pallas(x_, w_, b_, 1e-5,
                                             interpret=True) ** 2)

        def loss_ref(x_, w_, b_):
            return jnp.sum(layer_norm(x_, w_, b_, 1e-5, -1) ** 2)

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
        for a, r in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-4, atol=2e-4)


class TestFlashAttention:
    def _reference(self, q, k, v, causal=False):
        from paddle_tpu.ops.attention import scaled_dot_product_attention
        return scaled_dot_product_attention(q, k, v, causal=causal)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward(self, rng, causal):
        from paddle_tpu.kernels.flash_attention import flash_attention

        q = rng.standard_normal((2, 2, 128, 64)).astype(np.float32)
        k = rng.standard_normal((2, 2, 128, 64)).astype(np.float32)
        v = rng.standard_normal((2, 2, 128, 64)).astype(np.float32)
        ref = self._reference(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal)
        got = flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal, None, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_multi_block_seq(self, rng):
        """Sequence longer than one K block exercises the online softmax."""
        from paddle_tpu.kernels import flash_attention as fa
        orig_q, orig_k = fa.BLOCK_Q, fa.BLOCK_K
        fa.BLOCK_Q, fa.BLOCK_K = 64, 64
        try:
            q = rng.standard_normal((1, 1, 256, 32)).astype(np.float32)
            k = rng.standard_normal((1, 1, 256, 32)).astype(np.float32)
            v = rng.standard_normal((1, 1, 256, 32)).astype(np.float32)
            ref = self._reference(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), True)
            got = fa.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), True, None, True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
        finally:
            fa.BLOCK_Q, fa.BLOCK_K = orig_q, orig_k

    def test_unaligned_seq_k(self, rng):
        """seq not divisible by the K block — tail masking must hold."""
        from paddle_tpu.kernels import flash_attention as fa
        orig_q, orig_k = fa.BLOCK_Q, fa.BLOCK_K
        fa.BLOCK_Q, fa.BLOCK_K = 64, 64
        try:
            q = rng.standard_normal((1, 1, 100, 32)).astype(np.float32)
            k = rng.standard_normal((1, 1, 100, 32)).astype(np.float32)
            v = rng.standard_normal((1, 1, 100, 32)).astype(np.float32)
            ref = self._reference(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v))
            got = fa.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), False, None, True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
        finally:
            fa.BLOCK_Q, fa.BLOCK_K = orig_q, orig_k

    def test_causal_cross_length(self, rng):
        """tq != tk causal: bottom-right alignment must match reference."""
        from paddle_tpu.kernels import flash_attention as fa
        orig_q, orig_k = fa.BLOCK_Q, fa.BLOCK_K
        fa.BLOCK_Q, fa.BLOCK_K = 32, 32
        try:
            q = rng.standard_normal((1, 1, 32, 16)).astype(np.float32)
            k = rng.standard_normal((1, 1, 96, 16)).astype(np.float32)
            v = rng.standard_normal((1, 1, 96, 16)).astype(np.float32)
            ref = self._reference(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), True)
            got = fa.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), True, None, True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
        finally:
            fa.BLOCK_Q, fa.BLOCK_K = orig_q, orig_k

    def test_backward_matches_reference(self, rng):
        from paddle_tpu.kernels.flash_attention import flash_attention

        q = rng.standard_normal((1, 2, 64, 32)).astype(np.float32)
        k = rng.standard_normal((1, 2, 64, 32)).astype(np.float32)
        v = rng.standard_normal((1, 2, 64, 32)).astype(np.float32)

        def loss_flash(q_, k_, v_):
            return jnp.sum(flash_attention(q_, k_, v_, False, None, True)
                           ** 2)

        def loss_ref(q_, k_, v_):
            return jnp.sum(self._reference(q_, k_, v_) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("tq,tk", [(256, 256), (100, 100), (32, 96)])
    def test_backward_blocked(self, rng, causal, tq, tk):
        """Pallas backward across block boundaries, unaligned tails and
        cross-length causal (bottom-right alignment) — grads must match
        jax.grad through the XLA reference attention."""
        from paddle_tpu.kernels import flash_attention as fa
        orig_q, orig_k = fa.BLOCK_Q, fa.BLOCK_K
        fa.BLOCK_Q, fa.BLOCK_K = 64, 64
        try:
            q = rng.standard_normal((1, 2, tq, 32)).astype(np.float32)
            k = rng.standard_normal((1, 2, tk, 32)).astype(np.float32)
            v = rng.standard_normal((1, 2, tk, 32)).astype(np.float32)

            def loss_flash(q_, k_, v_):
                return jnp.sum(
                    fa.flash_attention(q_, k_, v_, causal, None, True)
                    ** 2)

            def loss_ref(q_, k_, v_):
                return jnp.sum(self._reference(q_, k_, v_, causal) ** 2)

            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
            gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
            for a, b, name in zip(gf, gr, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3,
                    err_msg=f"d{name} tq={tq} tk={tk} causal={causal}")
        finally:
            fa.BLOCK_Q, fa.BLOCK_K = orig_q, orig_k

    def test_backward_bf16(self, rng):
        """bf16 inputs (the production dtype): grads come back bf16 and
        close to the fp32 reference at bf16 tolerance."""
        from paddle_tpu.kernels.flash_attention import flash_attention

        q = rng.standard_normal((1, 2, 128, 64)).astype(np.float32)
        k = rng.standard_normal((1, 2, 128, 64)).astype(np.float32)
        v = rng.standard_normal((1, 2, 128, 64)).astype(np.float32)
        qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))

        def loss_flash(q_, k_, v_):
            return jnp.sum(
                flash_attention(q_, k_, v_, False, None, True)
                .astype(jnp.float32) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
        assert all(g.dtype == jnp.bfloat16 for g in gf)

        def loss_ref(q_, k_, v_):
            return jnp.sum(self._reference(q_, k_, v_) ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a, dtype=np.float32), np.asarray(b),
                rtol=0.1, atol=0.1)


class TestFlashAttentionDropout:
    """In-kernel attention dropout: the keep mask is a pure hash of
    (seed, head, position), so the forward mask can be EXTRACTED by
    running with v = I (output rows become the dropped+scaled prob
    rows) and the backward verified against a same-mask reference."""

    def _probs_and_mask(self, q, k, dropout_p, seed, causal=False):
        """Returns (ref_probs, keep_mask) via the v=I trick."""
        from paddle_tpu.kernels.flash_attention import flash_attention
        t = q.shape[2]
        eye = jnp.broadcast_to(jnp.eye(t, dtype=q.dtype),
                               q.shape[:2] + (t, t))
        dropped = flash_attention(q, k, eye, causal, None, True,
                                  dropout_p, seed)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (q.shape[-1]**0.5)
        ref_probs = jax.nn.softmax(logits, axis=-1)
        return np.asarray(ref_probs), np.asarray(dropped) != 0.0

    def test_mask_statistics_and_exactness(self, rng):
        from paddle_tpu.kernels import flash_attention as fa
        orig = fa.BLOCK_Q, fa.BLOCK_K
        fa.BLOCK_Q, fa.BLOCK_K = 32, 32
        try:
            pd = 0.25
            q = jnp.asarray(rng.standard_normal((1, 2, 64, 64)),
                            jnp.float32)
            k = jnp.asarray(rng.standard_normal((1, 2, 64, 64)),
                            jnp.float32)
            seed = jnp.asarray([[123]], jnp.int32)
            probs, keep = self._probs_and_mask(q, k, pd, seed)
            # kept entries carry EXACTLY prob/(1-pd); dropped are zero
            eye = jnp.broadcast_to(jnp.eye(64, dtype=q.dtype),
                                   (1, 2, 64, 64))
            out = np.asarray(fa.flash_attention(q, k, eye, False, None,
                                                True, pd, seed))
            expect = np.where(keep, probs / (1 - pd), 0.0)
            np.testing.assert_allclose(out, expect, rtol=2e-4, atol=1e-6)
            # keep rate approximates 1-pd (8192 Bernoulli draws)
            rate = keep.mean()
            assert abs(rate - (1 - pd)) < 0.03, rate
            # a different seed gives a different mask; same seed, same mask
            _, keep2 = self._probs_and_mask(q, k, pd,
                                            jnp.asarray([[77]], jnp.int32))
            assert (keep2 != keep).mean() > 0.05
            _, keep3 = self._probs_and_mask(q, k, pd, seed)
            np.testing.assert_array_equal(keep, keep3)
            # heads see different masks (head index feeds the hash)
            assert (keep[0, 0] != keep[0, 1]).mean() > 0.05
        finally:
            fa.BLOCK_Q, fa.BLOCK_K = orig

    def test_backward_matches_same_mask_reference(self, rng):
        from paddle_tpu.kernels import flash_attention as fa
        orig = fa.BLOCK_Q, fa.BLOCK_K
        fa.BLOCK_Q, fa.BLOCK_K = 32, 32
        try:
            pd = 0.2
            q = jnp.asarray(rng.standard_normal((1, 2, 64, 64)),
                            jnp.float32)
            k = jnp.asarray(rng.standard_normal((1, 2, 64, 64)),
                            jnp.float32)
            v = jnp.asarray(rng.standard_normal((1, 2, 64, 64)),
                            jnp.float32)
            w = jnp.asarray(rng.standard_normal((1, 2, 64, 64)),
                            jnp.float32)
            seed = jnp.asarray([[5]], jnp.int32)
            _, keep = self._probs_and_mask(q, k, pd, seed)
            keep = jnp.asarray(keep)

            def loss_flash(q_, k_, v_):
                out = fa.flash_attention(q_, k_, v_, False, None, True,
                                         pd, seed)
                return jnp.sum(out * w)

            def loss_ref(q_, k_, v_):
                logits = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) \
                    / (q_.shape[-1] ** 0.5)
                p = jax.nn.softmax(logits, axis=-1)
                p = jnp.where(keep, p / (1 - pd), 0.0)
                return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v_) * w)

            lf = loss_flash(q, k, v)
            lr_ = loss_ref(q, k, v)
            np.testing.assert_allclose(float(lf), float(lr_), rtol=2e-4)
            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
            for a, b, name in zip(gf, gr, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3,
                    err_msg=f"d{name}")
        finally:
            fa.BLOCK_Q, fa.BLOCK_K = orig

    def test_causal_dropout_backward(self, rng):
        """Dropout composed with causal masking and unaligned tails."""
        from paddle_tpu.kernels import flash_attention as fa
        orig = fa.BLOCK_Q, fa.BLOCK_K
        fa.BLOCK_Q, fa.BLOCK_K = 32, 32
        try:
            pd = 0.15
            tq = tk = 80  # unaligned tail
            q = jnp.asarray(rng.standard_normal((1, 1, tq, 80)),
                            jnp.float32)
            k = jnp.asarray(rng.standard_normal((1, 1, tk, 80)),
                            jnp.float32)
            v = jnp.asarray(rng.standard_normal((1, 1, tk, 80)),
                            jnp.float32)
            seed = jnp.asarray([[9]], jnp.int32)
            probs, keep = self._probs_and_mask(q, k, pd, seed,
                                               causal=True)
            keep = jnp.asarray(keep)

            def loss_flash(q_, k_, v_):
                out = fa.flash_attention(q_, k_, v_, True, None, True,
                                         pd, seed)
                return jnp.sum(out ** 2)

            def loss_ref(q_, k_, v_):
                logits = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) \
                    / (q_.shape[-1] ** 0.5)
                cm = jnp.tril(jnp.ones((tq, tk), bool))
                logits = jnp.where(cm, logits, -1e30)
                p = jax.nn.softmax(logits, axis=-1)
                p = jnp.where(keep, p / (1 - pd), 0.0)
                return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v_) ** 2)

            np.testing.assert_allclose(float(loss_flash(q, k, v)),
                                       float(loss_ref(q, k, v)),
                                       rtol=2e-4)
            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
            for a, b, name in zip(gf, gr, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3,
                    err_msg=f"d{name}")
        finally:
            fa.BLOCK_Q, fa.BLOCK_K = orig


class TestFlashWithLse:
    def test_lse_outputs_and_grads(self, rng):
        """(out, lse) variant: lse matches logsumexp of scaled logits and
        BOTH cotangents flow (the lse cotangent folds into delta)."""
        from paddle_tpu.kernels.flash_attention import \
            flash_attention_with_lse

        q = jnp.asarray(rng.standard_normal((1, 2, 64, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 64, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 64, 32)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((1, 2, 64, 32)), jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((1, 2, 64)), jnp.float32)
        scale = 1.0 / (32 ** 0.5)

        def loss_flash(q_, k_, v_):
            o, lse = flash_attention_with_lse(q_, k_, v_, False, None,
                                              True)
            return jnp.sum(o * w1) + jnp.sum(lse * w2)

        def loss_ref(q_, k_, v_):
            logits = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * scale
            p = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, v_)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            return jnp.sum(o * w1) + jnp.sum(lse * w2)

        np.testing.assert_allclose(float(loss_flash(q, k, v)),
                                   float(loss_ref(q, k, v)), rtol=2e-4)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3,
                err_msg=f"d{name}")


class TestFlashKvBias:
    """Key-padding mask as in-kernel additive bias."""

    def test_matches_masked_reference(self, rng):
        from paddle_tpu.kernels import flash_attention as fa
        from paddle_tpu.ops.attention import scaled_dot_product_attention
        orig = fa.BLOCK_Q, fa.BLOCK_K
        fa.BLOCK_Q, fa.BLOCK_K = 32, 32
        try:
            b, h, t, d = 2, 2, 96, 32
            q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            # per-example valid lengths 60 and 96
            lens = np.array([60, 96])
            keep = (np.arange(t)[None, :] < lens[:, None])
            bias = jnp.asarray(np.where(keep, 0.0, -1e30), jnp.float32)
            mask4 = bias[:, None, None, :]
            ref = scaled_dot_product_attention(q, k, v, mask=mask4)
            got = fa.flash_attention(q, k, v, False, None, True, 0.0,
                                     None, bias)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)

            # grads: padded-key columns must get zero dk/dv
            def loss_flash(q_, k_, v_):
                return jnp.sum(fa.flash_attention(
                    q_, k_, v_, False, None, True, 0.0, None, bias) ** 2)

            def loss_ref(q_, k_, v_):
                return jnp.sum(scaled_dot_product_attention(
                    q_, k_, v_, mask=mask4) ** 2)

            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
            for a, c, name in zip(gf, gr, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(c), rtol=2e-3, atol=2e-3,
                    err_msg=f"d{name}")
            assert np.abs(np.asarray(gf[1])[0, :, 60:, :]).max() == 0.0
        finally:
            fa.BLOCK_Q, fa.BLOCK_K = orig

    def test_bias_with_dropout_and_causal(self, rng):
        """bias + causal + in-kernel dropout compose: same-mask
        reference built from the extracted keep mask."""
        from paddle_tpu.kernels import flash_attention as fa
        orig = fa.BLOCK_Q, fa.BLOCK_K
        fa.BLOCK_Q, fa.BLOCK_K = 32, 32
        try:
            b, h, t = 1, 2, 64
            d = t  # v=I mask extraction needs square
            pd = 0.2
            q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            seed = jnp.asarray([[3]], jnp.int32)
            keep_keys = (np.arange(t) < 50)
            bias = jnp.asarray(np.where(keep_keys, 0.0, -1e30),
                               jnp.float32)[None, :]
            eye = jnp.broadcast_to(jnp.eye(t, dtype=q.dtype),
                                   (b, h, t, t))
            dropped = np.asarray(fa.flash_attention(
                q, k, eye, True, None, True, pd, seed, bias))
            keep_drop = jnp.asarray(dropped != 0.0)

            def loss_flash(q_, k_, v_):
                return jnp.sum(fa.flash_attention(
                    q_, k_, v_, True, None, True, pd, seed, bias) ** 2)

            def loss_ref(q_, k_, v_):
                logits = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) \
                    / (d ** 0.5) + bias[:, None, None, :]
                cm = jnp.tril(jnp.ones((t, t), bool))
                logits = jnp.where(cm, logits, -1e30)
                p = jax.nn.softmax(logits, axis=-1)
                p = jnp.where(keep_drop, p / (1 - pd), 0.0)
                return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v_) ** 2)

            np.testing.assert_allclose(float(loss_flash(q, k, v)),
                                       float(loss_ref(q, k, v)),
                                       rtol=2e-4)
            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
            for a, c, name in zip(gf, gr, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(c), rtol=2e-3, atol=2e-3,
                    err_msg=f"d{name}")
        finally:
            fa.BLOCK_Q, fa.BLOCK_K = orig


def test_mask_to_kv_bias_helpers():
    """Routing-layer mask conversion is pure and CPU-testable: bool
    masks are KEEP masks (True=attend -> bias 0, False -> -1e30);
    float masks pass through additively; only exact [B,1,1,Tk] shapes
    qualify (broadcastable shapes fall back to the XLA path)."""
    from paddle_tpu.kernels import _is_key_padding_mask, _mask_to_kv_bias

    m_bool = jnp.asarray(np.array(
        [[True] * 10 + [False] * 6, [True] * 16])[:, None, None, :])
    assert _is_key_padding_mask(m_bool, batch=2, tk=16)
    bias = np.asarray(_mask_to_kv_bias(m_bool))
    assert (bias[0, :10] == 0).all()
    assert (bias[0, 10:] < -1e29).all()
    assert (bias[1] == 0).all()
    m_add = jnp.zeros((2, 1, 1, 16), jnp.float32) - 5.0
    np.testing.assert_allclose(np.asarray(_mask_to_kv_bias(m_add)), -5.0)
    assert not _is_key_padding_mask(jnp.zeros((1, 1, 1, 16)), 2, 16)
    assert not _is_key_padding_mask(jnp.zeros((2, 1, 1, 8)), 2, 16)
    assert not _is_key_padding_mask(jnp.zeros((2, 1, 8, 16)), 2, 16)



def test_train_step_through_flash_path(monkeypatch):
    """End-to-end: a BERT train step with attention routed through the
    Pallas flash kernel (interpret mode), in-kernel dropout seeded from
    the traced RNG stream, under jit + grad + donated state — the exact
    integration the chip exercises at long sequence. Loss trajectory
    must track the XLA-attention step closely (same per-layer dropout
    stream, different mask bits, so trajectories agree loosely but both
    must decrease)."""
    import functools

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import kernels
    from paddle_tpu.kernels import flash_attention as fa_mod
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   pretraining_loss)
    from paddle_tpu.static import TrainStep

    config = BertConfig(num_hidden_layers=2, hidden_size=64,
                        num_attention_heads=2, intermediate_size=128,
                        vocab_size=512, max_position_embeddings=64)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, (2, 64)).astype(np.int32)
    mlm = rng.integers(0, 512, (2, 64)).astype(np.int64)
    nsp = rng.integers(0, 2, (2,)).astype(np.int64)

    prior_min_seq = pt.get_flags("flash_attention_min_seq")[
        "flash_attention_min_seq"]

    def run(flash: bool):
        if flash:
            monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
            monkeypatch.setattr(
                fa_mod, "flash_attention",
                functools.partial(fa_mod.flash_attention,
                                  interpret=True))
            pt.set_flags({"flash_attention_min_seq": 1})
        try:
            pt.seed(0)
            m = BertForPretraining(config)
            o = pt.optimizer.AdamW(learning_rate=1e-3)
            step = TrainStep(m, o, lambda out, a, b:
                             pretraining_loss(out, a, b))
            return [float(step(ids, labels=(mlm, nsp))["loss"])
                    for _ in range(4)]
        finally:
            if flash:
                pt.set_flags(
                    {"flash_attention_min_seq": prior_min_seq})
                monkeypatch.undo()

    base = run(False)
    fl = run(True)
    assert base[-1] < base[0], base
    assert fl[-1] < fl[0], fl
    # same model/data/optimizer; only attention impl + dropout bits
    # differ — trajectories must agree to dropout-noise tolerance
    np.testing.assert_allclose(fl, base, rtol=0.1)


def test_flash_block_size_flags_parity():
    """flash_block_q/k tiles are a pure performance lever: any tile
    choice (including non-divisible sequence tails) computes the same
    attention as the XLA reference."""
    import numpy as np

    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.kernels.flash_attention import flash_attention
    from paddle_tpu.ops.attention import scaled_dot_product_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (1, 2, 300, 64)), jnp.float32)
    ref = np.asarray(scaled_dot_product_attention(q, q, q))
    saved = pt.get_flags(["flash_block_q", "flash_block_k"])
    try:
        for bq, bk in [(64, 128), (128, 64), (32, 32)]:
            pt.set_flags({"flash_block_q": bq, "flash_block_k": bk})
            got = flash_attention(q, q, q, interpret=True)
            np.testing.assert_allclose(np.asarray(got), ref,
                                       rtol=2e-5, atol=2e-5)
    finally:
        pt.set_flags(saved)


def test_flash_train_eval_split_crossover(monkeypatch):
    """flash_attention_min_seq_train routes TRAINING attention to flash
    independently of the eval threshold (the XLA backward's fp32 [T,T]
    probs make the train crossover lower); 0 falls back to the shared
    flag. d=128 so the head-dim gate passes in BOTH modes — otherwise
    the eval assertions would hold vacuously."""
    import numpy as np

    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import kernels
    from paddle_tpu.kernels import flash_attention as fa_mod
    from paddle_tpu.kernels import maybe_flash_attention

    q = jnp.asarray(
        np.random.default_rng(0).normal(0, 1, (1, 2, 64, 128)),
        jnp.float32)
    calls = []
    orig = fa_mod.flash_attention

    def spy(*a, **k):
        calls.append(1)
        k.pop("interpret", None)
        return orig(*a, interpret=True, **k)

    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa_mod, "flash_attention", spy)
    saved = pt.get_flags(["flash_attention_min_seq",
                          "flash_attention_min_seq_train"])
    try:
        # eval threshold passes at d=128 (sanity: gate is live)
        pt.set_flags({"flash_attention_min_seq": 64,
                      "flash_attention_min_seq_train": 0})
        maybe_flash_attention(q, q, q, training=False)
        assert calls, "eval gate not live at d=128 — test is vacuous"
        calls.clear()
        # split: train threshold met, eval threshold not
        pt.set_flags({"flash_attention_min_seq": 4096,
                      "flash_attention_min_seq_train": 64})
        maybe_flash_attention(q, q, q, training=True)
        assert calls, "training did not route to flash at its threshold"
        calls.clear()
        maybe_flash_attention(q, q, q, training=False)
        assert not calls, "eval wrongly took the train threshold"
        # 0-sentinel: training falls back to the SHARED threshold
        # (4096 > 64 -> must NOT route)
        pt.set_flags({"flash_attention_min_seq": 4096,
                      "flash_attention_min_seq_train": 0})
        maybe_flash_attention(q, q, q, training=True)
        assert not calls, "train 0-sentinel ignored the shared threshold"
    finally:
        pt.set_flags(saved)


def test_flash_bthd_layout_parity(rng):
    """bthd=True takes [B, T, H, D] (the projections' native layout) and
    must match the [B, H, T, D] path bitwise: same kernels, the head
    gather just moves into the BlockSpec index maps. Covers forward and
    all three input grads, with causal + dropout + key bias + a
    non-block-multiple sequence (padding path)."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    b, h, t, d = 2, 4, 96, 64
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    bias = (jnp.where(jnp.arange(t)[None, :] < t - 7, 0.0, -1e30)
            .astype(jnp.float32) * jnp.ones((b, 1)))
    qT, kT, vT = (jnp.moveaxis(x, 1, 2) for x in (q, k, v))

    o_ref = flash_attention(q, k, v, interpret=True, kv_bias=bias)
    o_bthd = flash_attention(qT, kT, vT, interpret=True, kv_bias=bias,
                             bthd=True)
    np.testing.assert_array_equal(np.asarray(o_ref),
                                  np.asarray(jnp.moveaxis(o_bthd, 1, 2)))

    seed = jnp.asarray(5, jnp.int32)

    def loss(q_, k_, v_, bthd):
        out = flash_attention(q_, k_, v_, True, None, True, 0.1, seed,
                              bias, bthd)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g_ref = jax.grad(lambda a, b_, c: loss(a, b_, c, False),
                     argnums=(0, 1, 2))(q, k, v)
    g_bthd = jax.grad(lambda a, b_, c: loss(a, b_, c, True),
                      argnums=(0, 1, 2))(qT, kT, vT)
    for gr, gt in zip(g_ref, g_bthd):
        np.testing.assert_array_equal(np.asarray(gr),
                                      np.asarray(jnp.moveaxis(gt, 1, 2)))


def test_mha_bthd_routing_equivalence(monkeypatch):
    """MultiHeadAttention feeds attention in BTHD layout; when flash
    routes (train gate met) the module output must match the XLA
    composition run on the same inputs — layout plumbing must not
    change the math."""
    import paddle_tpu as pt
    from paddle_tpu import kernels
    from paddle_tpu.kernels import flash_attention as fa_mod
    from paddle_tpu.nn.layers.transformer import MultiHeadAttention

    pt.seed(0)
    # head dim 128 (256/2): the d%128 route is live in eval mode
    mha = MultiHeadAttention(256, 2, dropout=0.0)
    mha.eval()
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (2, 32, 256)),
                    jnp.float32)
    ref = np.asarray(mha(x))

    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    orig = fa_mod.flash_attention
    calls = []

    def spy(*a, **kw):
        calls.append(kw.get("bthd", False))
        kw.pop("interpret", None)
        return orig(*a, interpret=True, **kw)

    monkeypatch.setattr(kernels, "flash_attention", None, raising=False)
    monkeypatch.setattr(fa_mod, "flash_attention", spy)
    saved = pt.get_flags(["flash_attention_min_seq"])
    try:
        pt.set_flags({"flash_attention_min_seq": 16})
        got = np.asarray(mha(x))
    finally:
        pt.set_flags(saved)
    assert calls and calls[0] is True, \
        "MHA did not route the BTHD layout to flash"
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_fused_single_block_backward_matches_scanning(rng):
    """The fused single-block backward (default tiles, T <= tile) must
    produce the same gradients as the scanning two-kernel path (forced
    small tiles) under causal + dropout + key bias — the exact branch
    combination the production BERT config runs. Locks the fused
    kernel's inline mask/dropout/bias math to the scanning kernels'."""
    from paddle_tpu.kernels import flash_attention as fa

    b, h, t, d = 2, 2, 96, 64
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    bias = (jnp.where(jnp.arange(t)[None, :] < t - 5, 0.0, -1e30)
            .astype(jnp.float32) * jnp.ones((b, 1)))
    seed = jnp.asarray(11, jnp.int32)

    def grads(q_, k_, v_):
        return jax.grad(
            lambda a, b_, c: jnp.sum(fa.flash_attention(
                a, b_, c, True, None, True, 0.1, seed, bias) ** 2),
            argnums=(0, 1, 2))(q_, k_, v_)

    g_fused = grads(q, k, v)          # default 512 tiles -> fused path
    orig_q, orig_k = fa.BLOCK_Q, fa.BLOCK_K
    fa.BLOCK_Q, fa.BLOCK_K = 32, 32   # multi-block -> scanning path
    try:
        g_scan = grads(q, k, v)
    finally:
        fa.BLOCK_Q, fa.BLOCK_K = orig_q, orig_k
    for gf, gs, name in zip(g_fused, g_scan, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gs),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


# -- the tile walk: whole tiles without a mask, edges under theirs -------------
#
# The scanning kernels walk a run of whole tiles through a body that
# builds no mask and the edges (the causal diagonal, a tile with a
# padded tail) through the masked one. A mask wrong by a tile shows here
# and hardly anywhere else: the cells' comparisons see it only over a
# sequence's first tiles.

def _dense_attention(q, k, v, causal, bias=None, keep=None, pd=0.0):
    """[B, H, T, D] dense reference, bottom-right causal alignment."""
    tq, tk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (q.shape[-1] ** 0.5)
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        allowed = (jnp.arange(tq)[:, None] + (tk - tq)
                   >= jnp.arange(tk)[None, :])
        s = jnp.where(allowed, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if keep is not None:
        p = jnp.where(keep, p / (1 - pd), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("bthd", [False, True], ids=["bhtd", "bthd"])
@pytest.mark.parametrize("with_bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("tq,tk,causal", [
    (128, 128, False), (128, 128, True), (112, 112, False),
    (112, 112, True), (64, 160, False), (64, 160, True), (160, 96, False)])
def test_tile_walk_matches_dense(rng, monkeypatch, tq, tk, causal,
                                 with_bias, bthd):
    """Several tiles a side, with and without a tail (``tk % 32``),
    queries shorter and longer than keys, a key bias on whole tiles
    too: output and the three gradients against dense attention."""
    from paddle_tpu.kernels import flash_attention as fa
    monkeypatch.setattr(fa, "BLOCK_Q", 32)
    monkeypatch.setattr(fa, "BLOCK_K", 32)
    b, h, d = 2, 2, 64
    q = jnp.asarray(rng.standard_normal((b, h, tq, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b, h, tk, d)), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.standard_normal((b, h, tq, d)), jnp.float32)
    bias = None
    if with_bias:       # keys hidden in the middle of whole tiles
        hidden = (np.arange(tk)[None, :] % 7 == np.array([[1], [4]]))
        hidden[:, 0] = False
        bias = jnp.asarray(np.where(hidden, -1e30, 0.0), jnp.float32)
    swap = (lambda x: jnp.moveaxis(x, 1, 2)) if bthd else (lambda x: x)

    def kernel(q_, k_, v_):
        out = fa.flash_attention(swap(q_), swap(k_), swap(v_), causal, None,
                                 True, 0.0, None, bias, bthd)
        return jnp.sum(swap(out) * w), swap(out)

    def dense(q_, k_, v_):
        out = _dense_attention(q_, k_, v_, causal, bias)
        return jnp.sum(out * w), out

    (_, got), got_g = jax.value_and_grad(kernel, (0, 1, 2), True)(q, k, v)
    (_, want), want_g = jax.value_and_grad(dense, (0, 1, 2), True)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    for a, c, name in zip(got_g, want_g, "qkv"):
        np.testing.assert_allclose(a, c, rtol=2e-3, atol=2e-3,
                                   err_msg="d" + name)


@pytest.mark.parametrize("tq,tk,causal", [
    (128, 128, False), (128, 128, True), (100, 100, False),
    (100, 100, True), (32, 96, True), (96, 32, False), (64, 64, True)])
def test_tile_census_counts_the_dense_rule(tq, tk, causal):
    """Visited: the tiles that hold an allowed pair. Whole: those of
    them with every pair allowed and no padded key."""
    from paddle_tpu.kernels import flash_attention as fa
    tile = 32
    allowed = np.ones((tq, tk), bool)
    if causal:
        allowed = (np.arange(tq)[:, None] + (tk - tq)
                   >= np.arange(tk)[None, :])
    visited = whole = 0
    for i in range(0, tq, tile):
        for j in range(0, tk, tile):
            cut = allowed[i:i + tile, j:j + tile]
            visited += bool(cut.any())
            whole += bool(cut.all() and j + tile <= tk)
            assert bool(fa.tile_whole(i, j, tile, tk, causal, tk - tq)) \
                == bool(cut.all() and j + tile <= tk), (i, j)
    assert fa.flash_tile_census(tq, tk, tile, tile, causal) == (
        visited, whole, 0)


def _np_fmix32(x):
    x = np.asarray(x, np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _hashed_keep(seed, head_id, tq, tk, pd):
    """The keep mask of one head as PR 28 fixed it: a hash of (seed,
    head, query position, key position), written here in numpy."""
    with np.errstate(over="ignore"):
        h = _np_fmix32(np.uint32(seed) ^ _np_fmix32(
            np.uint32(head_id) + np.uint32(0x9E3779B9)))
        u = _np_fmix32(np.arange(tq, dtype=np.uint32)[:, None] + h)
        bits = _np_fmix32(u ^ (np.arange(tk, dtype=np.uint32)[None, :]
                               * np.uint32(0x9E3779B9)))
    return bits >= np.uint32(min(int(pd * 4294967296.0), 4294967295))


# sha256 of the packed keep mask that the parent of PR 38 drew for the
# case below (`_two_heads_a_program_keep` run on its tree)
_PARENT_KEEP_SHA256 = \
    "ca77bb3bf7ce739eef28de0cadcb7ace9b57f8abec4afc60396247f7fa571d47"


def _two_heads_a_program_keep(fa, tile):
    """The keep mask two 64-wide heads a program draw at seed 20260905,
    by the v = I trick: [B, H, T, T] bool."""
    b, t, h, d, pd = 2, 64, 4, 64, 0.1
    rng = np.random.default_rng(7)
    q, k = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
            for _ in range(2))
    eye = jnp.broadcast_to(jnp.eye(t, dtype=jnp.float32)[:, None, :],
                           (b, t, h, t))
    orig = fa.BLOCK_Q, fa.BLOCK_K
    fa.BLOCK_Q, fa.BLOCK_K = tile, tile
    try:
        out = fa.flash_attention(q, k, eye, False, None, True, pd,
                                 jnp.asarray(20260905, jnp.int32), None,
                                 True)
    finally:
        fa.BLOCK_Q, fa.BLOCK_K = orig
    return np.moveaxis(np.asarray(out) != 0.0, 2, 1)


@pytest.mark.parametrize("tile", [32, 512], ids=["scanning", "one_tile"])
def test_dropout_mask_is_bitwise_the_parents(tile):
    """Whole tiles build no position for a mask, and the hash still
    counts by its own: two heads a program (BERT's layout), the mask
    equal bit for bit to the hash written out in numpy and to what the
    parent commit drew."""
    import hashlib

    from paddle_tpu.kernels import flash_attention as fa
    keep = _two_heads_a_program_keep(fa, tile)
    b, h, t = keep.shape[:3]
    want = np.stack([np.stack([
        _hashed_keep(20260905, bi * h + hi, t, t, 0.1) for hi in range(h)])
        for bi in range(b)])
    np.testing.assert_array_equal(keep, want)
    assert hashlib.sha256(np.packbits(keep).tobytes()).hexdigest() \
        == _PARENT_KEEP_SHA256


def test_two_heads_a_program_dropout_gradients_match_dense(rng):
    """BERT's case on the scanning kernels: two 64-wide heads a program,
    dropout 0.1, several tiles: output and gradients against dense
    attention under the same (hashed) mask."""
    from paddle_tpu.kernels import flash_attention as fa
    b, t, h, d, pd = 2, 96, 2, 64, 0.1
    q, k, v, w = (jnp.asarray(rng.standard_normal((b, h, t, d)),
                              jnp.float32) for _ in range(4))
    seed = 99
    keep = jnp.asarray(np.stack([np.stack([
        _hashed_keep(seed, bi * h + hi, t, t, pd) for hi in range(h)])
        for bi in range(b)]))
    swap = lambda x: jnp.moveaxis(x, 1, 2)                # noqa: E731
    orig = fa.BLOCK_Q, fa.BLOCK_K
    fa.BLOCK_Q, fa.BLOCK_K = 32, 32
    try:
        def kernel(q_, k_, v_):
            out = fa.flash_attention(swap(q_), swap(k_), swap(v_), False,
                                     None, True, pd,
                                     jnp.asarray(seed, jnp.int32), None,
                                     True)
            return jnp.sum(swap(out) * w), swap(out)

        def dense(q_, k_, v_):
            out = _dense_attention(q_, k_, v_, False, keep=keep, pd=pd)
            return jnp.sum(out * w), out

        (_, got), got_g = jax.value_and_grad(kernel, (0, 1, 2), True)(
            q, k, v)
        (_, want), want_g = jax.value_and_grad(dense, (0, 1, 2), True)(
            q, k, v)
    finally:
        fa.BLOCK_Q, fa.BLOCK_K = orig
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    for a, c, name in zip(got_g, want_g, "qkv"):
        np.testing.assert_allclose(a, c, rtol=2e-3, atol=2e-3,
                                   err_msg="d" + name)
