"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _close(got, want, tol):
    """Largest difference within ``tol`` of the largest |want|."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-6)
    assert float(jnp.max(jnp.abs(got - want)) / scale) < tol


def _value_and_vjp(fn, q, k, v, do):
    o, vjp = jax.vjp(fn, q, k, v)
    return (o,) + vjp(do)


class TestGaloreKernel:
    @pytest.mark.parametrize("m,n,r", [(128, 128, 8), (256, 128, 32),
                                       (128, 256, 16), (512, 128, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, m, n, r, dtype):
        ks = jax.random.split(KEY, 5)
        w = jax.random.normal(ks[0], (m, n), dtype)
        g = jax.random.normal(ks[1], (m, n), dtype)
        basis = jnp.linalg.qr(jax.random.normal(ks[2], (n, r)))[0]
        mm = 0.1 * jax.random.normal(ks[3], (m, r), jnp.float32)
        vv = 0.01 * jnp.abs(jax.random.normal(ks[4], (m, r), jnp.float32))
        out_k = ops.galore_adamw_step(w, g, basis, mm, vv, 5.0,
                                      lr=1e-2, weight_decay=0.01)
        out_r = ref.galore_adamw_ref(w, g, basis, mm, vv, count=5.0,
                                     lr=1e-2, weight_decay=0.01)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        for a, b in zip(out_k, out_r):
            assert jnp.allclose(a.astype(jnp.float32),
                                b.astype(jnp.float32), atol=tol), (m, n, r)

    def test_block_rows_invariance(self):
        ks = jax.random.split(KEY, 5)
        m, n, r = 256, 128, 8
        w = jax.random.normal(ks[0], (m, n))
        g = jax.random.normal(ks[1], (m, n))
        basis = jnp.linalg.qr(jax.random.normal(ks[2], (n, r)))[0]
        mm = jnp.zeros((m, r)); vv = jnp.zeros((m, r))
        a = ops.galore_adamw_step(w, g, basis, mm, vv, 1.0, block_rows=64)
        b = ops.galore_adamw_step(w, g, basis, mm, vv, 1.0, block_rows=256)
        assert jnp.allclose(a[0], b[0], atol=1e-5)

    @pytest.mark.parametrize("m,block_rows", [(96, 64), (100, 32), (7, 8)])
    def test_odd_rows_masked_tail(self, m, block_rows):
        """Row counts that don't divide block_rows run on a ceil-div grid
        with a masked tail tile (regression for the old hard assert)."""
        n, r = 256, 8
        ks = jax.random.split(KEY, 5)
        w = jax.random.normal(ks[0], (m, n))
        g = jax.random.normal(ks[1], (m, n))
        basis = jnp.linalg.qr(jax.random.normal(ks[2], (n, r)))[0]
        mm = 0.1 * jax.random.normal(ks[3], (m, r), jnp.float32)
        vv = 0.01 * jnp.abs(jax.random.normal(ks[4], (m, r), jnp.float32))
        out_k = ops.galore_adamw_step(w, g, basis, mm, vv, 5.0, lr=1e-2,
                                      weight_decay=0.01,
                                      block_rows=block_rows)
        out_r = ref.galore_adamw_ref(w, g, basis, mm, vv, count=5.0, lr=1e-2,
                                     weight_decay=0.01)
        for a, b in zip(out_k, out_r):
            assert jnp.allclose(a, b, atol=1e-5), (m, block_rows)

    @pytest.mark.parametrize("m,n,block", [(64, 200, 64), (32, 256, 128)])
    def test_left_projected_block(self, m, n, block):
        """Left blocks (m < n): basis (m, r), moments (r, n), column tiling."""
        r = 8
        ks = jax.random.split(KEY, 5)
        w = jax.random.normal(ks[0], (m, n))
        g = jax.random.normal(ks[1], (m, n))
        basis = jnp.linalg.qr(jax.random.normal(ks[2], (m, r)))[0]
        mm = 0.1 * jax.random.normal(ks[3], (r, n), jnp.float32)
        vv = 0.01 * jnp.abs(jax.random.normal(ks[4], (r, n), jnp.float32))
        out_k = ops.galore_adamw_step(w, g, basis, mm, vv, 3.0, lr=1e-2,
                                      weight_decay=0.01, block_rows=block)
        gt = basis.T @ g
        m_new = 0.9 * mm + 0.1 * gt
        v_new = 0.999 * vv + 0.001 * gt * gt
        ut = (m_new / (1 - 0.9 ** 3.0)) / (
            jnp.sqrt(v_new / (1 - 0.999 ** 3.0)) + 1e-8)
        u = basis @ ut
        w_ref = w - 1e-2 * u - 1e-2 * 0.01 * w
        for a, b in zip(out_k, (w_ref, m_new, v_new)):
            assert jnp.allclose(a, b, atol=1e-5), (m, n, block)

    def test_stacked_3d_blocks(self):
        """Stacked scan blocks (nb, m, n) match per-layer 2-D calls."""
        nb, m, n, r = 3, 96, 128, 8
        ks = jax.random.split(KEY, 5)
        w = jax.random.normal(ks[0], (nb, m, n))
        g = jax.random.normal(ks[1], (nb, m, n))
        basis = jnp.stack([jnp.linalg.qr(jax.random.normal(
            jax.random.fold_in(ks[2], i), (n, r)))[0] for i in range(nb)])
        mm = 0.1 * jax.random.normal(ks[3], (nb, m, r), jnp.float32)
        vv = 0.01 * jnp.abs(jax.random.normal(ks[4], (nb, m, r), jnp.float32))
        out = ops.galore_adamw_step(w, g, basis, mm, vv, 2.0, lr=1e-2,
                                    block_rows=64)
        for i in range(nb):
            exp = ref.galore_adamw_ref(w[i], g[i], basis[i], mm[i], vv[i],
                                       count=2.0, lr=1e-2)
            for a, b in zip(out, exp):
                assert jnp.allclose(a[i], b, atol=1e-5), i

    def test_precond_matches_full_step(self):
        """galore_precond_step returns the same moments and an update u with
        w - lr*u == the full step's weight output (weight_decay=0)."""
        m, n, r = 96, 256, 8
        ks = jax.random.split(KEY, 5)
        w = jax.random.normal(ks[0], (m, n))
        g = jax.random.normal(ks[1], (m, n))
        basis = jnp.linalg.qr(jax.random.normal(ks[2], (n, r)))[0]
        mm = 0.1 * jax.random.normal(ks[3], (m, r), jnp.float32)
        vv = 0.01 * jnp.abs(jax.random.normal(ks[4], (m, r), jnp.float32))
        lr = 1e-2
        w_new, m_full, v_full = ops.galore_adamw_step(
            w, g, basis, mm, vv, 5.0, lr=lr, weight_decay=0.0, block_rows=64)
        u, m_pre, v_pre = ops.galore_precond_step(g, basis, mm, vv, 5.0,
                                                  block_rows=64)
        assert jnp.allclose(m_pre, m_full, atol=1e-6)
        assert jnp.allclose(v_pre, v_full, atol=1e-6)
        assert jnp.allclose(w - lr * u, w_new, atol=1e-5)

    @pytest.mark.parametrize("side,shape", [("right", (96, 256)),
                                            ("left", (256, 96))])
    def test_precond_projected_output(self, side, shape):
        """project_back=False returns ũ in the moment shape with
        lift(ũ) == the ambient u of the default path, same moments — the
        factored-delta client contract."""
        m, n = shape
        r = 8
        dim = n if side == "right" else m
        mv_shape = (m, r) if side == "right" else (r, n)
        ks = jax.random.split(KEY, 4)
        g = jax.random.normal(ks[0], (m, n))
        basis = jnp.linalg.qr(jax.random.normal(ks[1], (dim, r)))[0]
        mm = 0.1 * jax.random.normal(ks[2], mv_shape, jnp.float32)
        vv = 0.01 * jnp.abs(jax.random.normal(ks[3], mv_shape, jnp.float32))
        u, m_a, v_a = ops.galore_precond_step(g, basis, mm, vv, 5.0,
                                              block_rows=64)
        ut, m_p, v_p = ops.galore_precond_step(g, basis, mm, vv, 5.0,
                                               block_rows=64,
                                               project_back=False)
        assert ut.shape == mv_shape
        assert jnp.allclose(m_p, m_a, atol=1e-6)
        assert jnp.allclose(v_p, v_a, atol=1e-6)
        lifted = ut @ basis.T if side == "right" else basis @ ut
        assert jnp.allclose(lifted, u, atol=1e-5)


    @pytest.mark.parametrize("project_back", [True, False])
    @pytest.mark.parametrize("side,shape", [("right", (200, 128)),
                                            ("left", (128, 200))])
    def test_precond_matches_ref(self, side, shape, project_back):
        """Both sides, ambient and projected output, against the two-sided
        oracle the chip smoke check also uses."""
        m, n = shape
        r = 8
        dim, mv_shape = (n, (m, r)) if side == "right" else (m, (r, n))
        ks = jax.random.split(KEY, 4)
        g = jax.random.normal(ks[0], (m, n))
        basis = jnp.linalg.qr(jax.random.normal(ks[1], (dim, r)))[0]
        mm = 0.1 * jax.random.normal(ks[2], mv_shape, jnp.float32)
        vv = 0.01 * jnp.abs(jax.random.normal(ks[3], mv_shape, jnp.float32))
        got = ops.galore_precond_step(g, basis, mm, vv, 5.0, block_rows=64,
                                      project_back=project_back)
        want = ref.galore_precond_ref(g, basis, mm, vv, count=5.0, side=side,
                                      project_back=project_back)
        for a, b in zip(got, want):
            assert a.shape == b.shape
            assert jnp.allclose(a, b, atol=1e-5)

    def test_bias_correction_off(self):
        """bias_correction=False divides by exact ones."""
        m, n, r = 64, 128, 8
        ks = jax.random.split(KEY, 4)
        g = jax.random.normal(ks[0], (m, n))
        basis = jnp.linalg.qr(jax.random.normal(ks[1], (n, r)))[0]
        mm = 0.1 * jax.random.normal(ks[2], (m, r), jnp.float32)
        vv = 0.01 * jnp.abs(jax.random.normal(ks[3], (m, r), jnp.float32))
        ut, m_new, v_new = ops.galore_precond_step(
            g, basis, mm, vv, 7.0, project_back=False, bias_correction=False)
        assert jnp.allclose(ut, m_new / (jnp.sqrt(v_new) + 1e-8), atol=1e-6)


class TestKernelDispatch:
    """``ops.use_kernels``: the kernels replace their XLA formulations on a
    TPU backend, except under an ambient mesh of several devices, whose
    partitioned program cannot hold a Pallas call."""

    def test_cpu_backend_keeps_xla(self):
        assert not ops.use_kernels()

    @pytest.mark.parametrize("mesh_shape,want", [(None, True),
                                                 ((1, 1), True),
                                                 ((4, 1), False),
                                                 ((2, 2), False)])
    def test_tpu_rule(self, monkeypatch, mesh_shape, want):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        if mesh_shape is None:
            assert ops.use_kernels() is want
            return
        mesh = jax.sharding.AbstractMesh(mesh_shape, ("data", "model"))
        with jax.sharding.use_abstract_mesh(mesh):
            assert ops.use_kernels() is want


class TestFlashAttention:
    @pytest.mark.parametrize("lq,lk,h,hkv,d", [
        (128, 128, 4, 4, 64),      # MHA square
        (128, 256, 4, 2, 64),      # GQA + longer KV (decode-suffix style)
        (256, 256, 8, 2, 128),     # GQA 4:1, MXU-width head
    ])
    @pytest.mark.parametrize("window", [0, 64])
    def test_matches_ref(self, lq, lk, h, hkv, d, window):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (2, lq, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (2, lk, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (2, lk, hkv, d), jnp.float32)
        o_k = ops.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=64, block_k=64)
        o_r = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        assert jnp.allclose(o_k, o_r, atol=2e-5), (lq, lk, h, hkv, d, window)

    def test_bf16(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 64), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.bfloat16)
        o_k = ops.flash_attention(q, k, v, block_q=64, block_k=64)
        o_r = ref.flash_attention_ref(q, k, v)
        assert jnp.allclose(o_k.astype(jnp.float32),
                            o_r.astype(jnp.float32), atol=3e-2)

    def test_matches_model_attention(self):
        """Kernel output == the model's einsum attention (same masking)."""
        from repro.models.attention import attend, causal_mask
        ks = jax.random.split(KEY, 3)
        b, l, h, d = 2, 128, 4, 64
        q = jax.random.normal(ks[0], (b, l, h, d))
        k = jax.random.normal(ks[1], (b, l, 2, d))
        v = jax.random.normal(ks[2], (b, l, 2, d))
        pos = jnp.arange(l)
        mask = causal_mask(pos, pos)[None]
        o_model = attend(q, k, v, mask)
        o_kernel = ops.flash_attention(q, k, v, block_q=64, block_k=64)
        assert jnp.allclose(o_model, o_kernel, atol=2e-5)

    @staticmethod
    def _qkv(b, l, h, hkv, d, dtype, n=4, lead=()):
        ks = jax.random.split(jax.random.PRNGKey(7), n)
        shapes = [lead + (b, l, h, d), lead + (b, l, hkv, d),
                  lead + (b, l, hkv, d), lead + (b, l, h, d)]
        return [jax.random.normal(kk, s, jnp.float32).astype(dtype)
                for kk, s in zip(ks, shapes)]

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                           (jnp.bfloat16, 3e-2)],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "bidirectional"])
    @pytest.mark.parametrize("h,hkv", [(2, 2), (4, 2)], ids=["mha", "gqa"])
    @pytest.mark.parametrize("l", [128, 256])
    def test_value_and_grad_match_attend(self, l, h, hkv, causal, dtype,
                                         tol):
        """Forward and the gradient in q, k and v against the model's
        ``attend`` (same masks, bf16 operands with fp32 accumulation)."""
        from repro.models.attention import attend, causal_mask
        q, k, v, do = self._qkv(1, l, h, hkv, 64, dtype)
        mask = causal_mask(jnp.arange(l), jnp.arange(l))[None] if causal \
            else None

        got = jax.jit(lambda: _value_and_vjp(
            lambda q, k, v: ops.flash_attention(q, k, v, causal=causal),
            q, k, v, do))()
        want = jax.jit(lambda: _value_and_vjp(
            lambda q, k, v: attend(q, k, v, mask), q, k, v, do))()
        for g, w in zip(got, want):
            _close(g, w, tol)

    def test_grad_under_vmap_and_checkpoint(self):
        """A vmapped client axis and a remat block around the kernel, as
        the federated round runs it."""
        from repro.models.attention import attend, causal_mask
        q, k, v, do = self._qkv(1, 256, 4, 2, 64, jnp.float32, lead=(2,))
        mask = causal_mask(jnp.arange(256), jnp.arange(256))[None]

        def grads(fn):
            def loss(q, k, v):
                return jnp.sum(jax.checkpoint(jax.vmap(fn))(q, k, v) * do)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        got = grads(lambda q, k, v: ops.flash_attention(q, k, v))
        want = grads(lambda q, k, v: attend(q, k, v, mask))
        for g, w in zip(got, want):
            _close(g, w, 1e-5)

    @pytest.mark.parametrize("lq,lk,window", [(256, 256, 64),
                                              (128, 384, 200)])
    def test_window_grad_matches_ref(self, lq, lk, window):
        """Sliding windows and suffix queries, forward and backward, against
        the fp32 reference; the tiles are skipped and masked per edge."""
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (1, lq, 4, 64))
        k = jax.random.normal(ks[1], (1, lk, 2, 64))
        v = jax.random.normal(ks[2], (1, lk, 2, 64))
        do = jax.random.normal(ks[3], (1, lq, 4, 64))

        got = jax.jit(lambda: _value_and_vjp(
            lambda q, k, v: ops.flash_attention(q, k, v, window=window),
            q, k, v, do))()
        want = _value_and_vjp(lambda q, k, v: ref.flash_attention_ref(
            q, k, v, window=window), q, k, v, do)
        for g, w in zip(got, want):
            _close(g, w, 1e-5)


class TestFlashRoute:
    """``models.attention.flash_route``: which full-sequence shapes take
    the flash kernel on the chip, and that ``gqa_forward`` follows it."""

    @pytest.mark.parametrize("lq,lk,window,chunk,mesh_shape,want", [
        (512, 512, 0, 4096, None, True),      # the qwen cell
        (512, 512, 0, 0, None, True),         # no blockwise chunk
        (128, 128, 0, 4096, None, True),      # one tile
        (1, 512, 0, 4096, None, False),       # decode: one query
        (384, 512, 0, 4096, None, False),     # not square
        (200, 200, 0, 4096, None, False),     # not whole 128-row tiles
        (512, 512, 64, 4096, None, False),    # sliding window
        (4096, 4096, 0, 4096, None, False),   # blockwise_attend from here
        (512, 512, 0, 4096, (4, 1), False),   # partitioned over a mesh
        (512, 512, 0, 4096, (1, 1), True),    # a one-device mesh
    ])
    def test_rule(self, monkeypatch, lq, lk, window, chunk, mesh_shape,
                  want):
        from repro.models.attention import flash_route
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        if mesh_shape is None:
            assert flash_route(lq, lk, window, chunk) is want
            return
        mesh = jax.sharding.AbstractMesh(mesh_shape, ("data", "model"))
        with jax.sharding.use_abstract_mesh(mesh):
            assert flash_route(lq, lk, window, chunk) is want

    def test_cpu_keeps_attend(self):
        from repro.models.attention import flash_route
        assert not flash_route(512, 512, 0, 4096)

    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "bidirectional"])
    def test_gqa_forward_on_the_route(self, monkeypatch, causal):
        """``gqa_forward`` with the kernels forced on (interpret mode here)
        gives the loss and parameter gradients of the ``attend`` path."""
        from repro.models import attention
        p = attention.gqa_init(KEY, 128, 4, 2, 32, qkv_bias=True)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 128))

        def loss(p):
            out, _ = attention.gqa_forward(
                p, x, jnp.arange(128), n_heads=4, n_kv=2, head_dim=32,
                attn_chunk=4096, causal=causal)
            return jnp.sum(out ** 2)

        want = jax.value_and_grad(loss)(p)
        monkeypatch.setattr(ops, "use_kernels", lambda: True)
        assert attention.flash_route(128, 128, 0, 4096)
        got = jax.value_and_grad(loss)(p)
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
        for name in want[1]:
            _close(got[1][name], want[1][name], 1e-4)


class TestRwkv6Kernel:
    @pytest.mark.parametrize("l,h,d,chunk", [(64, 2, 64, 32), (128, 4, 64, 64),
                                             (64, 1, 128, 64)])
    def test_matches_ref(self, l, h, d, chunk):
        ks = jax.random.split(KEY, 5)
        shape = (2, l, h, d)
        r = 0.5 * jax.random.normal(ks[0], shape)
        k = 0.5 * jax.random.normal(ks[1], shape)
        v = 0.5 * jax.random.normal(ks[2], shape)
        w = jax.nn.sigmoid(jax.random.normal(ks[3], shape))
        u = 0.1 * jax.random.normal(ks[4], (h, d))
        y_k, s_k = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk)
        y_r, s_r = ref.rwkv6_scan_ref(r, k, v, w, u)
        assert jnp.allclose(y_k, y_r, atol=1e-4)
        assert jnp.allclose(s_k, s_r, atol=1e-4)

    def test_initial_state_carried(self):
        ks = jax.random.split(KEY, 6)
        shape = (1, 32, 2, 64)
        r, k, v = (0.3 * jax.random.normal(ks[i], shape) for i in range(3))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], shape))
        u = 0.1 * jax.random.normal(ks[4], (2, 64))
        s0 = 0.5 * jax.random.normal(ks[5], (1, 2, 64, 64))
        y_k, s_k = ops.rwkv6_scan(r, k, v, w, u, s0, chunk=32)
        y_r, s_r = ref.rwkv6_scan_ref(r, k, v, w, u, s0)
        assert jnp.allclose(y_k, y_r, atol=1e-4)
        assert jnp.allclose(s_k, s_r, atol=1e-4)

    def test_kernel_matches_model_layer_math(self):
        """The kernel recurrence == the RWKV layer's scan recurrence."""
        from repro.models import rwkv as rw
        d_model = 128
        h = d_model // rw.HEAD_SIZE
        ks = jax.random.split(KEY, 5)
        shape = (1, 16, h, rw.HEAD_SIZE)
        r = 0.3 * jax.random.normal(ks[0], shape)
        k = 0.3 * jax.random.normal(ks[1], shape)
        v = 0.3 * jax.random.normal(ks[2], shape)
        w = jax.nn.sigmoid(jax.random.normal(ks[3], shape))
        u = 0.1 * jax.random.normal(ks[4], (h, rw.HEAD_SIZE))
        y_kernel, _ = ops.rwkv6_scan(r, k, v, w, u, chunk=16)
        y_ref, _ = ref.rwkv6_scan_ref(r, k, v, w, u)
        assert jnp.allclose(y_kernel, y_ref, atol=1e-4)
