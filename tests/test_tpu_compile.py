"""Real-TPU Mosaic compile gate for every Pallas kernel (VERDICT r2 item 2).

≙ SURVEY.md §4 two-platform rule: every kernel must not only pass math
checks in interpret mode but COMPILE for the attached chip. Round 2
shipped a norm kernel whose BlockSpec Mosaic rejected — interpret-mode CI
could not see it and the bench went to 0.0. This suite jits and EXECUTES
each kernel (fwd AND bwd) so any Mosaic rejection fails the suite, not
the bench. tests/test_mosaic_lowering.py compiles the same kernels for a
v5e topology without a chip; only this gate also RUNS them.

Two shape families: the 255M bench shape (hidden 1024, 16Q/8KV,
head_dim 64) and head_dim 128 at 32Q/8KV and 16Q/16KV — what every
configuration in ROADMAP Queue 2 uses.

Runs only under PDT_TEST_PLATFORM=tpu with a real chip attached; skips
cleanly on the CPU CI mesh. Through the tool, in one call:
`PDT_TEST_PLATFORM=tpu python -m pytest tests/test_tpu_compile.py -q`.
Tests that need two or four devices skip on the one-chip machine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.devices()[0].platform != "tpu",
    reason="Mosaic compile gate needs the real TPU chip",
)

# bench.py's Llama config: hidden 1024, 16 q heads / 8 kv heads, d=64,
# batch 8, seq 2048 -> norm rows 16384 (the exact shape that failed r2)
BENCH_B, BENCH_S, BENCH_H, BENCH_HK, BENCH_D = 8, 2048, 16, 8, 64
BENCH_HIDDEN = 1024
BENCH_ROWS = BENCH_B * BENCH_S
# (q heads, kv heads, head_dim): the bench family, then head_dim 128 as
# GQA group 4 and as MHA
HEAD_FAMILIES = [(BENCH_H, BENCH_HK, BENCH_D), (32, 8, 128),
                 (16, 16, 128)]


def _compile(fn, *args):
    """jit + run + wait: any Mosaic rejection (trace-time or chip
    compile) and any runtime fault raises here."""
    return jax.block_until_ready(jax.jit(fn)(*args))


def _need_devices(n):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, found {jax.device_count()}")


class TestNormKernelsCompile:
    def test_rms_norm_fwd_bwd_bench_shape(self):
        from paddle_tpu.ops.norm_kernels import rms_norm_values

        x = jnp.zeros((BENCH_ROWS, BENCH_HIDDEN), jnp.bfloat16)
        w = jnp.ones((BENCH_HIDDEN,), jnp.bfloat16)
        _compile(rms_norm_values, x, w)

        def loss(x, w):
            return rms_norm_values(x, w).astype(jnp.float32).sum()

        _compile(jax.grad(loss, argnums=(0, 1)), x, w)

    def test_layer_norm_fwd_bwd_bench_shape(self):
        from paddle_tpu.ops.norm_kernels import layer_norm_values

        x = jnp.zeros((BENCH_ROWS, BENCH_HIDDEN), jnp.bfloat16)
        w = jnp.ones((BENCH_HIDDEN,), jnp.bfloat16)
        b = jnp.zeros((BENCH_HIDDEN,), jnp.bfloat16)
        _compile(layer_norm_values, x, w, b)

        def loss(x, w, b):
            return layer_norm_values(x, w, b).astype(jnp.float32).sum()

        _compile(jax.grad(loss, argnums=(0, 1, 2)), x, w, b)

    def test_rms_norm_runs_and_matches_xla(self):
        from paddle_tpu.ops.norm_kernels import rms_norm_values

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((512, BENCH_HIDDEN)),
                        jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal(BENCH_HIDDEN), jnp.bfloat16)
        out = _compile(rms_norm_values, x, w)
        xf = x.astype(jnp.float32)
        ref = (xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), -1, keepdims=True) + 1e-6)
            * w.astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=0.05)


class TestFlashAttentionCompile:
    def _qkv(self, heads=HEAD_FAMILIES[0], batch=BENCH_B):
        h, hk, d = heads
        q = jnp.zeros((batch, BENCH_S, h, d), jnp.bfloat16)
        k = jnp.zeros((batch, BENCH_S, hk, d), jnp.bfloat16)
        v = jnp.zeros((batch, BENCH_S, hk, d), jnp.bfloat16)
        return q, k, v

    @pytest.mark.parametrize("heads", HEAD_FAMILIES)
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_bwd_gqa(self, causal, heads):
        from paddle_tpu.ops.flash_attention import flash_attention_values

        q, k, v = self._qkv(heads, batch=2 if heads[2] == 128 else BENCH_B)
        _compile(lambda q, k, v: flash_attention_values(
            q, k, v, causal=causal), q, k, v)

        def loss(q, k, v):
            return flash_attention_values(
                q, k, v, causal=causal).astype(jnp.float32).sum()

        _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    def test_sliding_window_fwd_bwd_bench_shape(self):
        from paddle_tpu.ops.flash_attention import flash_attention_values

        q, k, v = self._qkv()
        _compile(lambda q, k, v: flash_attention_values(
            q, k, v, causal=True, window_size=512), q, k, v)

        def loss(q, k, v):
            return flash_attention_values(
                q, k, v, causal=True,
                window_size=512).astype(jnp.float32).sum()

        _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


class TestRopeCompile:
    def test_fwd_bwd_bench_shape(self):
        from paddle_tpu.ops.rope import rope_values

        x = jnp.zeros((BENCH_B, BENCH_S, BENCH_H, BENCH_D), jnp.bfloat16)
        # trig tables are (max_len, D/2) — the kernel's pair convention
        # (rope_values docstring; models/llama.py precompute_rope)
        cos = jnp.zeros((BENCH_S, BENCH_D // 2), jnp.float32)
        sin = jnp.zeros((BENCH_S, BENCH_D // 2), jnp.float32)
        _compile(rope_values, x, cos, sin)

        def loss(x):
            return rope_values(x, cos, sin).astype(jnp.float32).sum()

        _compile(jax.grad(loss), x)


class TestVarlenFlashCompile:
    def test_fwd_bwd_packed_bench_shape(self):
        from paddle_tpu.ops.flash_varlen import flash_attention_varlen_values

        q = jnp.zeros((BENCH_B, BENCH_S, BENCH_H, BENCH_D), jnp.bfloat16)
        k = jnp.zeros((BENCH_B, BENCH_S, BENCH_HK, BENCH_D), jnp.bfloat16)
        seg = jnp.zeros((BENCH_B, BENCH_S), jnp.int32)

        def loss(q, k, v):
            return flash_attention_varlen_values(
                q, k, v, seg, seg, causal=True).astype(jnp.float32).sum()

        _compile(lambda q, k, v: flash_attention_varlen_values(
            q, k, v, seg, seg, causal=True), q, k, k)
        _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)


class TestPagedAttentionCompile:
    @pytest.mark.parametrize("heads", HEAD_FAMILIES)
    def test_decode_shape(self, heads):
        from paddle_tpu.ops.paged_attention import paged_attention_values

        h, hk, d = heads
        b, pages_per_seq, page = 8, 128, 16   # 2048-token contexts
        q = jnp.zeros((b, h, d), jnp.bfloat16)
        kp = jnp.zeros((b * pages_per_seq, page, hk * d), jnp.bfloat16)
        bt = jnp.arange(b * pages_per_seq, dtype=jnp.int32).reshape(
            b, pages_per_seq)
        cl = jnp.full((b,), 2000, jnp.int32)
        _compile(lambda q, kp, vp: paged_attention_values(
            q, kp, vp, cl, bt), q, kp, kp)
        # sliding-window band variant (serving window models on paged)
        _compile(lambda q, kp, vp: paged_attention_values(
            q, kp, vp, cl, bt, window=512), q, kp, kp)


class TestRaggedPagedAttentionCompile:
    """ISSUE 6: the mixed prefill+decode ragged kernel must compile AND
    execute on the chip — q tiles are (block_q*G, D), descriptors ride
    scalar prefetch, dead pages route their index_map to the trash
    page. Numerics vs the XLA oracle stay the interpret tier's job
    (tests/test_ragged_attention.py); this is the Mosaic gate."""

    PPS, PAGE = 128, 16                       # 2048-token contexts

    def _mixed(self, heads, dtype=jnp.bfloat16):
        """A mixed batch: two 512-token prefills and four decode rows."""
        from paddle_tpu.ops.ragged_paged_attention import \
            pack_ragged_starts
        h, hk, d = heads
        ql = np.array([512, 512, 1, 1, 1, 1], np.int32)
        cl = np.array([512, 512, 1800, 1500, 900, 600], np.int32)
        qs, total = pack_ragged_starts(ql, block_q=8)
        q = jnp.zeros((total, h, d), jnp.bfloat16)
        kp = jnp.zeros((len(ql) * self.PPS + 1, self.PAGE, hk * d), dtype)
        bt = 1 + jnp.arange(len(ql) * self.PPS, dtype=jnp.int32).reshape(
            len(ql), self.PPS)
        return q, kp, qs, ql, cl, bt

    def _decode(self, heads, dtype=jnp.bfloat16, b=8):
        h, hk, d = heads
        qs = np.arange(b, dtype=np.int32)
        ql = np.ones(b, np.int32)
        cl = np.full(b, 2000, np.int32)
        q = jnp.zeros((b, h, d), jnp.bfloat16)
        kp = jnp.zeros((b * self.PPS + 1, self.PAGE, hk * d), dtype)
        bt = 1 + jnp.arange(b * self.PPS, dtype=jnp.int32).reshape(
            b, self.PPS)
        return q, kp, qs, ql, cl, bt

    @pytest.mark.parametrize("heads", HEAD_FAMILIES)
    def test_mixed_batch_and_decode_shapes(self, heads):
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values as rpa

        q, kp, qs, ql, cl, bt = self._mixed(heads)
        _compile(lambda q, kp, vp: rpa(q, kp, vp, qs, ql, cl, bt,
                                       block_q=8), q, kp, kp)
        _compile(lambda q, kp, vp: rpa(q, kp, vp, qs, ql, cl, bt,
                                       window=512, block_q=8), q, kp, kp)
        # decode form: block_q=1, one query per sequence
        q, kp, qs, ql, cl, bt = self._decode(heads)
        _compile(lambda q, kp, vp: rpa(q, kp, vp, qs, ql, cl, bt,
                                       block_q=1), q, kp, kp)

    @pytest.mark.parametrize("heads", HEAD_FAMILIES)
    @pytest.mark.parametrize("block_q", [8, 1])
    def test_int8_pages_with_scale_pools(self, block_q, heads):
        """ISSUE 15: int8 page pools with (P, page_size) f32 scale
        pools, dequantized per page in flight."""
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values as rpa

        q, kp, qs, ql, cl, bt = (self._mixed if block_q == 8
                                 else self._decode)(heads, jnp.int8)
        ks = jnp.ones(kp.shape[:2], jnp.float32)
        out = _compile(
            lambda q, kp, vp, ks, vs: rpa(q, kp, vp, qs, ql, cl, bt,
                                          block_q=block_q, k_scale=ks,
                                          v_scale=vs), q, kp, kp, ks, ks)
        assert np.isfinite(np.asarray(out, np.float32)).all()

    # the benchmark's cells: (Q heads, KV heads, slots, rows, table
    # columns, block_q), as tests/test_mosaic_lowering.py lists them
    CELL_SHAPES = {
        "batch.decode": (16, 8, 32, 32, 128, 1),
        "batch.admit512": (16, 8, 32, 512, 128, 8),
        "chat.decode": (32, 8, 32, 32, 64, 1),
        "chat.admit256": (32, 8, 32, 256, 64, 8),
        "reasoning.decode": (32, 2, 64, 64, 512, 1),
        "reasoning.admit1024": (32, 2, 64, 1024, 512, 8),
    }

    def _cell_case(self, shape, variant="bf16"):
        """A cell's shape on contexts of up to half the table, bf16 q:
        (q, kp, vp, descriptors, the block tables, the same with dead
        columns on page 0, keywords). The table's dead columns hold an
        id far outside the pool: a DMA that read one would fault."""
        from paddle_tpu.ops.ragged_paged_attention import \
            pack_ragged_starts
        h, hk, n, rows, pps, block_q = self.CELL_SHAPES[shape]
        d = 128
        rng = np.random.default_rng(rows + pps)
        cl = rng.integers(self.PAGE, pps * self.PAGE // 2, n)
        if block_q == 1:
            ql, qs = np.ones(n, np.int32), np.arange(n, dtype=np.int32)
        else:
            ql = np.zeros(n, np.int32)
            ql[:2] = rows // 2 - 40, rows // 2 - 9
            cl[:2] = ql[:2] + (0, 77)          # a prefill, a continuation
            qs, _ = pack_ragged_starts(ql, block_q=block_q)
        pages = n * pps + 1
        bt = np.full((n, pps), 2 ** 30, np.int32)
        safe = np.zeros((n, pps), np.int32)
        perm = rng.permutation(pages - 1) + 1
        for s in range(n):
            need = -(-int(cl[s]) // self.PAGE)
            bt[s, :need] = safe[s, :need] = perm[s * pps:s * pps + need]
        q = jnp.asarray(rng.standard_normal((rows, h, d)), jnp.bfloat16)
        shp = (pages, self.PAGE, hk * d)
        kw = {"window": 300} if variant == "window" else {}
        if variant == "int8":
            kp, vp = (jnp.asarray(rng.integers(-127, 128, shp), jnp.int8)
                      for _ in range(2))
            kw.update(
                k_scale=jnp.asarray(rng.uniform(0.002, 0.02, shp[:2]),
                                    jnp.float32),
                v_scale=jnp.asarray(rng.uniform(0.002, 0.02, shp[:2]),
                                    jnp.float32))
        else:
            kp, vp = (jnp.asarray(rng.standard_normal(shp, np.float32),
                                  jnp.bfloat16) for _ in range(2))
        return q, kp, vp, (qs, ql, cl), bt, safe, block_q, kw

    @pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
    @pytest.mark.parametrize("variant", ["bf16", "int8", "window"])
    def test_cell_shapes_match_the_oracle(self, variant, shape):
        """ISSUE 26: the in-kernel loop over KV blocks, executed at the
        benchmark cells' shapes against the XLA oracle."""
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values as rpa
        q, kp, vp, desc, bt, safe, block_q, kw = self._cell_case(
            shape, variant)
        got = _compile(lambda q, kp, vp: rpa(
            q, kp, vp, *desc, bt, block_q=block_q, **kw), q, kp, vp)
        want = _compile(lambda q, kp, vp: rpa(
            q, kp, vp, *desc, safe, block_q=block_q,
            use_kernel=False, **kw), q, kp, vp)
        # bf16 outputs of O(1) values; the oracle rounds its normalised
        # softmax weights to the pool's dtype, the kernel its running ones
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=3e-2)

    # max |kernel - float64 NumPy| at `_cell_case`'s data of the PARENT's
    # kernel (float32 MXU operands, one pass a head) on a TPU v5 lite,
    # rounded up in the fourth digit (PERF.md section 6, PR 30): the
    # bf16 output's rounding, and the softmax weights' that the MXU
    # made of its float32 operand
    PARENT_MAX_ERR = {
        "batch.decode": 0.004160,
        "batch.admit512": 0.007721,
        "chat.decode": 0.004595,
        "chat.admit256": 0.008602,
        "reasoning.decode": 0.001721,
        "reasoning.admit1024": 0.008350,
    }

    @staticmethod
    def _oracle64(q, kp, vp, desc, bt, page):
        """The attention of `_cell_case`'s bf16 data in float64 NumPy, a
        sequence at a time (no window, no scales)."""
        qs, ql, cl = desc
        q = np.asarray(q.astype(jnp.float32), np.float64)
        t, h, d = q.shape
        hk = kp.shape[2] // d
        out = np.zeros((t, h, d))
        for s in range(len(ql)):
            n, c = int(ql[s]), int(cl[s])
            if not n:
                continue
            pg = jnp.asarray(bt[s, :-(-c // page)])
            k, v = (np.asarray(x[pg].astype(jnp.float32), np.float64)
                    .reshape(-1, hk, d)[:c] for x in (kp, vp))
            qq = q[qs[s]:qs[s] + n].reshape(n, hk, h // hk, d)
            lg = np.einsum("nkgd,ckd->nkgc", qq, k) / np.sqrt(d)
            causal = np.arange(c)[None, :] <= (c - n + np.arange(n))[:, None]
            lg = np.where(causal[:, None, None, :], lg, -np.inf)
            p = np.exp(lg - lg.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[qs[s]:qs[s] + n] = np.einsum(
                "nkgc,ckd->nkgd", p, v).reshape(n, h, d)
        return out

    def _cell_error(self, shape, rpa):
        """max |rpa's output - float64| at a cell's shape; `rpa` is a
        parameter so that a parent's kernel can be measured the same
        way when `PARENT_MAX_ERR` is taken again."""
        q, kp, vp, desc, bt, _, block_q, _ = self._cell_case(shape)
        got = _compile(lambda q, kp, vp: rpa(
            q, kp, vp, *desc, bt, block_q=block_q), q, kp, vp)
        want = self._oracle64(q, kp, vp, desc, bt, self.PAGE)
        return float(np.abs(np.asarray(got, np.float64) - want).max())

    @pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
    def test_cell_shapes_error_no_larger_than_the_parents(self, shape):
        """ISSUE 30: bf16 operands at the MXU (q, K and V as stored,
        the softmax weights rounded to bf16 beside them) lose nothing
        the float32 operands kept on the chip, where the MXU rounded
        them the same way: the error against float64 at the cells'
        shapes is no larger than the parent's kernel's."""
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values as rpa
        assert self._cell_error(shape, rpa) <= self.PARENT_MAX_ERR[shape]

    @pytest.mark.parametrize("block_q", [8, 1])
    def test_tp_shard_map_kernel(self, block_q):
        """ISSUE 12: under tensor parallelism the kernel runs per head
        shard through shard_map (`_ragged_tp_shard_map`) — and must
        agree with the one-device kernel on the same inputs."""
        _need_devices(2)
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values as rpa

        heads = HEAD_FAMILIES[0]
        q, kp, qs, ql, cl, bt = (self._mixed if block_q == 8
                                 else self._decode)(heads)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal(q.shape), jnp.bfloat16)
        kp = jnp.asarray(rng.standard_normal(kp.shape), jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal(kp.shape), jnp.bfloat16)
        want = _compile(lambda q, kp, vp: rpa(
            q, kp, vp, qs, ql, cl, bt, block_q=block_q), q, kp, vp)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        qd = jax.device_put(q, NamedSharding(mesh, P(None, "tp", None)))
        pool = NamedSharding(mesh, P(None, None, "tp"))
        got = _compile(lambda q, kp, vp: rpa(
            q, kp, vp, qs, ql, cl, bt, block_q=block_q,
            tp=(mesh, "tp")), qd, jax.device_put(kp, pool),
            jax.device_put(vp, pool))
        assert {d.id for d in got.sharding.device_set} == \
            {d.id for d in jax.devices()[:2]}
        # the same kernel over the same heads, only split in two
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


class TestKernelsUnderAMesh:
    """Mosaic cannot partition a kernel ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    every kernel a multi-device program reaches must run per shard.
    The flash and norm kernels do, through `mesh.shard_kernel`. The
    quantised matmul and the LoRA epilogue under a TP replica do NOT
    yet: their two tests fail on a multi-chip host until they do
    (CHANGES.md, PR 21) — nothing routes around them."""

    def test_train_kernels_under_sharding_x_mp(self):
        _need_devices(4)
        import paddle_tpu.distributed as dist
        from paddle_tpu.ops.flash_attention import flash_attention_values
        from paddle_tpu.ops.norm_kernels import rms_norm_values

        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((4, 2048, 32, 64)),
                        jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((4, 2048, 8, 64)),
                        jnp.bfloat16)
        x = jnp.asarray(rng.standard_normal((4, 2048, 2048)),
                        jnp.bfloat16)
        w = jnp.ones((2048,), jnp.bfloat16)

        def grad():
            # a fresh function each time: jit caches a trace by the
            # function's identity, and the mesh the kernels read while
            # tracing is not part of that key
            def loss(q, k, v, x, w):
                return (flash_attention_values(q, k, v, causal=True)
                        .astype(jnp.float32).sum()
                        + rms_norm_values(x, w).astype(jnp.float32).sum())
            return jax.grad(loss, argnums=(0, 1, 2, 3, 4))

        want = _compile(grad(), q, k, k, x, w)
        with dist.use_mesh(dist.create_mesh(sharding=2, mp=2)):
            got = _compile(grad(), q, k, k, x, w)
        assert len(got[0].sharding.device_set) == 4
        for g, r in zip(got, want):
            # each shard rounds its partial dw to bf16 before the sum
            # over shards: a bf16 ulp of the largest entry, not of each
            r = np.asarray(r, np.float32)
            np.testing.assert_allclose(
                np.asarray(g, np.float32), r, rtol=2e-2,
                atol=2e-2 + 2.0 ** -7 * np.abs(r).max())

    def _tp_mesh(self):
        _need_devices(2)
        from jax.sharding import Mesh
        return Mesh(np.asarray(jax.devices()[:2]), ("tp",))

    def test_dequant_matmul_column_sharded(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.quant_matmul import dequant_matmul_values

        mesh = self._tp_mesh()
        x = jnp.ones((8, 2048), jnp.bfloat16)
        qw = jax.device_put(jnp.ones((2048, 8192), jnp.int8),
                            NamedSharding(mesh, P(None, "tp")))
        sc = jax.device_put(jnp.ones((8192,), jnp.float32),
                            NamedSharding(mesh, P("tp")))
        _compile(dequant_matmul_values, x, qw, sc)

    def test_lora_epilogue_column_sharded(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.lora_epilogue import lora_epilogue_values

        mesh = self._tp_mesh()
        x = jnp.ones((8, 2048), jnp.bfloat16)
        a = jnp.ones((4, 2048, 16), jnp.bfloat16)
        b = jax.device_put(jnp.ones((4, 16, 2048), jnp.bfloat16),
                           NamedSharding(mesh, P(None, None, "tp")))
        _compile(lora_epilogue_values, x, a, b,
                 jnp.ones((4,), jnp.float32), jnp.zeros((8,), jnp.int32))


class TestQuantAndLoraCompile:
    """ISSUE 15 / ISSUE 17 kernels at Llama-3.2-1B widths, decode and
    prefill row counts, against their own XLA paths."""

    @pytest.mark.parametrize("m", [8, 1504])
    def test_dequant_matmul(self, m):
        from paddle_tpu.ops.quant_matmul import (dequant_matmul_values,
                                                 quantize_weight_values)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((m, 2048)), jnp.bfloat16)
        qw, sc = quantize_weight_values(
            jnp.asarray(rng.standard_normal((2048, 8192)) * 0.02,
                        jnp.float32), "int8")
        got = _compile(dequant_matmul_values, x, qw, sc)
        want = _compile(lambda x, qw, sc: dequant_matmul_values(
            x, qw, sc, use_kernel=False), x, qw, sc)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("t", [8, 1504])
    def test_lora_epilogue(self, t):
        """One-row blocks ride a unit middle axis: the (1, K) block of
        a (T, K) array this kernel first shipped with does not lower
        for the TPU at all."""
        from paddle_tpu.ops.lora_epilogue import lora_epilogue_values
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((t, 2048)), jnp.bfloat16)
        a = jnp.asarray(rng.standard_normal((4, 2048, 16)) * 0.02,
                        jnp.bfloat16)
        b = jnp.asarray(rng.standard_normal((4, 16, 2048)) * 0.02,
                        jnp.bfloat16)
        sc = jnp.asarray([0.0, 1.0, 0.5, 2.0], jnp.float32)
        ids = jnp.asarray(rng.integers(0, 4, t), jnp.int32)
        got = _compile(lora_epilogue_values, x, a, b, sc, ids)
        want = _compile(lambda *args: lora_epilogue_values(
            *args, use_kernel=False), x, a, b, sc, ids)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)


class TestGroupedMatmulCompile:
    def test_gmm_bench_shape(self):
        from paddle_tpu.ops.grouped_matmul import gmm_pallas

        # MoE-ish: 8 experts, 4096 tokens, 1024 -> 2816
        lhs = jnp.zeros((4096, BENCH_HIDDEN), jnp.bfloat16)
        rhs = jnp.zeros((8, BENCH_HIDDEN, 2816), jnp.bfloat16)
        sizes = jnp.full((8,), 512, jnp.int32)
        _compile(gmm_pallas, lhs, rhs, sizes)

    # (assignments, K, N) on 128 held experts: the block cell's pass
    # (256 rows x 8: tile 16) and admission (512 x 8: tile 32), the
    # hybrid's decode step (64 x 22 / 4: tile 16)
    SERVED = {
        "generate.pass.gate_up": (2048, 2048, 1536),
        "generate.pass.down": (2048, 768, 2048),
        "generate.admit512.gate_up": (4096, 2048, 1536),
        "generate.admit512.down": (4096, 768, 2048),
        "reasoning.decode.up": (352, 1024, 2688),
        "reasoning.decode.down": (352, 2688, 1024),
    }

    @pytest.mark.parametrize("shape", sorted(SERVED))
    def test_served_shapes_run_and_match_the_xla_walk(self, shape):
        """ISSUE 32: the stationary grid at the served widths, rows
        drawn unevenly so that experts take one, two and three tiles,
        some none, with dead tiles behind them."""
        from paddle_tpu.ops import grouped_matmul as gm
        held = 128
        a, k, n = self.SERVED[shape]
        bm = gm.row_block(a / held)
        rng = np.random.default_rng(4)
        p = 1 / np.arange(1.0, held + 1)
        counts = rng.multinomial(a, rng.permutation(p / p.sum()))
        counts[rng.integers(0, held, 6)] = 0
        padded = -(-counts // bm) * bm
        m = -(-(a + held * (bm - 1)) // bm) * bm
        assert (padded // bm).max() >= 3 and padded.sum() < m
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((held, k, n)) * k ** -0.5,
                        jnp.bfloat16)
        sizes = jnp.asarray(padded, jnp.int32)
        got = _compile(lambda *args: gm.grouped_matmul_values(*args, bm),
                       x, w, sizes)
        want = _compile(lambda *args: gm._gmm_xla(*args, bm), x, w, sizes)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)
        assert not np.asarray(got[int(padded.sum()):], np.float32).any()


class TestInt8MXUCompile:
    """Round-4: the W8A8 path must hit the MXU's native int8 mode on
    the real chip (VERDICT r3 #4), and be FASTER than bf16 at a
    serving-ish shape."""

    def test_int8_dot_compiles_and_runs(self):
        from paddle_tpu.nn.quant import (int8_dot_values,
                                         quantize_activation_dynamic_values)

        x = jnp.zeros((BENCH_ROWS // 4, BENCH_HIDDEN), jnp.bfloat16)
        w8 = jnp.zeros((BENCH_HIDDEN, 4 * BENCH_HIDDEN), jnp.int8)
        ws = jnp.ones((4 * BENCH_HIDDEN,), jnp.float32)

        def f(xv):
            xq, xs = quantize_activation_dynamic_values(xv)
            return int8_dot_values(xq, w8, xs, ws)
        _compile(f, x)

    def test_weight_only_int8_decode_shape(self):
        from paddle_tpu.nn.quant import (weight_only_linear_values,
                                         weight_quantize_values)

        w = jnp.ones((BENCH_HIDDEN, 4 * BENCH_HIDDEN), jnp.float32)
        qw, sc = weight_quantize_values(w)
        x = jnp.zeros((BENCH_B, 1, BENCH_HIDDEN), jnp.bfloat16)  # decode
        _compile(lambda xv: weight_only_linear_values(
            xv.reshape(-1, BENCH_HIDDEN), qw, sc), x)

    def test_int8_faster_than_bf16_at_large_shape(self):
        """int8 must beat bf16 on the MXU at a large shape, by the slope
        method of bench.bench_int8 (N dependent matmuls inside ONE
        executable at two values of N: the slope cancels every fixed
        cost of a dispatch)."""
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "bench", os.path.join(os.path.dirname(__file__), "..",
                                  "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        res = bench.bench_int8(on_tpu=True)
        print(f"\n{res}")
        assert res["int8_speedup_vs_bf16"] > 1.05, res


class TestRaggedEPCompile:
    """Round-5: the ragged exact-EP exchange (count all-gather +
    lax.ragged_all_to_all) has no XLA:CPU thunk, so the chip is the only
    place it can EXECUTE. ep=1 on the single chip still runs the real
    ragged-all-to-all op (self-exchange) through the full dispatch/
    compute/return pipeline."""

    def test_ragged_ep_matches_single_shard_dropless(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.incubate.moe import (moe_ffn_dropless_ep_values,
                                             moe_ffn_dropless_values)

        e, h, i, k, t = 8, 256, 512, 2, 512
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((t, h)), jnp.float32)
        gw = jnp.asarray(rng.standard_normal((h, e)) * 0.1, jnp.float32)
        wg = jnp.asarray(rng.standard_normal((e, h, i)) * 0.05,
                         jnp.float32)
        wu = jnp.asarray(rng.standard_normal((e, h, i)) * 0.05,
                         jnp.float32)
        wd = jnp.asarray(rng.standard_normal((e, i, h)) * 0.05,
                         jnp.float32)

        mesh = Mesh(np.array(jax.devices()[:1]), ("ep",))

        def body(x_l, gw_, wg_l, wu_l, wd_l):
            return moe_ffn_dropless_ep_values(
                x_l, gw_, wg_l, wu_l, wd_l, k, 1, "ep", ["ep"], t * k,
                ragged=True)

        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(P("ep", None), P(None, None), P("ep", None, None),
                      P("ep", None, None), P("ep", None, None)),
            out_specs=(P("ep", None), P(), P()), check_vma=False)
        out, aux, drops = _compile(mapped, x, gw, wg, wu, wd)
        ref, aux_ref = _compile(
            lambda *a: moe_ffn_dropless_values(*a, k), x, gw, wg, wu, wd)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
        assert abs(float(aux) - float(aux_ref)) < 1e-3
        assert int(drops) == 0


class TestPagedEngineDecodeCompile:
    """The serving engine's decode path on the chip.

    Chip-gate r5 finding: asserting exact greedy-token equality between
    the engine and the dense-tuple reference is unsound on silicon — the
    Pallas kernel and the XLA dense attention are both correct but
    accumulate in different orders (measured max |Δ| = one bf16 ulp), and
    greedy argmax amplifies a near-tie into a different trajectory after
    ~10 tokens (interpret mode can't see this: both run the same XLA math
    there). So the chip test asserts (a) single-step LOGIT parity between
    a ragged decode step through the page table and `generate()`'s
    dense-tuple cache on identical cache state, and (b) the engine runs
    end-to-end producing well-formed outputs."""

    def _tiny(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                          intermediate_size=512, num_hidden_layers=2,
                          num_attention_heads=8, num_key_value_heads=4,
                          max_position_embeddings=512)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return cfg, m

    def test_ragged_decode_step_logits_match_dense_on_chip(self):
        from paddle_tpu.core.tensor import Tensor, no_grad
        from paddle_tpu.models.llama import RaggedKVCacheView
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_scatter_values

        cfg, m = self._tiny()
        hk, hd = cfg.num_key_value_heads, cfg.head_dim
        page_size, b, s_max = 16, 2, 256
        pps = s_max // page_size
        rng = np.random.default_rng(1)
        p_lens = [27, 41]                      # straddle page boundaries
        p_max = max(p_lens)
        ids = np.zeros((b, p_max), np.int64)
        for i, pl_ in enumerate(p_lens):
            ids[i, :pl_] = rng.integers(1, cfg.vocab_size, pl_)
        # the prompts' rows as one packed axis: owner and position
        seq = jnp.asarray(np.repeat(np.arange(b), p_lens), jnp.int32)
        pos = jnp.asarray(np.concatenate([np.arange(n) for n in p_lens]),
                          jnp.int32)
        bt = jnp.arange(1, 1 + b * pps, dtype=jnp.int32).reshape(b, pps)

        with no_grad():
            # dense prefill (zero caches + validity mask) -> per-layer
            # (B, S, HK, D) caches
            zero = [(Tensor(jnp.zeros((b, p_max, hk, hd), jnp.float32)),
                     Tensor(jnp.zeros((b, p_max, hk, hd), jnp.float32)))
                    for _ in range(cfg.num_hidden_layers)]
            am = jnp.arange(p_max)[None, :] < jnp.asarray(p_lens)[:, None]
            _, caches = m.forward(Tensor(jnp.asarray(ids)),
                                  attention_mask=Tensor(am),
                                  past_key_values=zero,
                                  position_offset=0, use_cache=True)
            dense, views = [], []
            n_pages = 1 + b * pps              # page 0 = trash page
            one = jnp.arange(b, dtype=jnp.int32)
            ctx = jnp.asarray(p_lens, jnp.int32)
            for (k, v) in caches:
                kd = jnp.zeros((b, s_max, hk, hd), k._value.dtype)
                vd = jnp.zeros_like(kd)
                kd = kd.at[:, :k.shape[1]].set(k._value)
                vd = vd.at[:, :v.shape[1]].set(v._value)
                dense.append((Tensor(kd), Tensor(vd)))
                kp = jnp.zeros((n_pages, page_size, hk * hd),
                               k._value.dtype)
                kp, vp = ragged_scatter_values(
                    kp, jnp.zeros_like(kp), k._value[seq, pos],
                    v._value[seq, pos], bt, seq, pos)
                # the decode descriptors: one query a sequence, at its
                # next position
                views.append(RaggedKVCacheView(
                    kp, vp, bt, one, ctx, one, jnp.ones_like(one),
                    ctx + 1, block_q=1))
            tok = jnp.asarray([[7], [11]], jnp.int64)

            lg_dense, _ = m.forward(Tensor(tok), past_key_values=dense,
                                    position_offset=Tensor(ctx),
                                    use_cache=True)
            lg_ragged, _ = m.forward(Tensor(tok.reshape(1, b)),
                                     past_key_values=views,
                                     use_cache=True)
        np.testing.assert_allclose(
            np.asarray(lg_ragged._value, np.float32)[0],
            np.asarray(lg_dense._value, np.float32)[:, 0],
            rtol=2e-2, atol=2e-2)

    def test_engine_runs_on_chip(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine

        cfg, m = self._tiny()
        rng = np.random.default_rng(1)
        prompts = [list(rng.integers(1, cfg.vocab_size, 12 + 5 * j))
                   for j in range(3)]
        eng = ContinuousBatchingEngine(m, max_batch_size=2,
                                       max_seq_len=256)
        rids = [eng.add_request(p, 16) for p in prompts]
        res = eng.run()
        assert sorted(res) == sorted(rids)
        for r in rids:
            assert len(res[r]) == 16
            assert all(0 <= t < cfg.vocab_size for t in res[r])

    def test_speculative_decode_on_chip(self):
        """Draft-propose + one-forward verify (vector-offset rope, s>1
        vector cache writes, in-graph verify mask) compiles and runs on
        silicon; output must stay lossless vs target greedy."""
        from paddle_tpu.models.speculative import speculative_generate
        import paddle_tpu as paddle
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        cfg, t = self._tiny()
        paddle.seed(1)
        d = LlamaForCausalLM(LlamaConfig(
            vocab_size=cfg.vocab_size, hidden_size=128,
            intermediate_size=256, num_hidden_layers=1,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=512))
        d.eval()
        ids = np.random.default_rng(3).integers(
            1, cfg.vocab_size, (2, 9)).astype(np.int32)
        want, _ = t.generate(paddle.to_tensor(ids), max_new_tokens=12)
        got, acc = speculative_generate(t, d, paddle.to_tensor(ids),
                                        max_new_tokens=12,
                                        num_draft_tokens=4)
        np.testing.assert_array_equal(np.asarray(got._value),
                                      np.asarray(want._value))

    def test_prefix_caching_suffix_prefill_on_chip(self):
        """The prefix-hit admission (the suffix's rows attend the
        attached pages at their own positions) must compile and run on
        silicon."""
        from paddle_tpu.models.serving import ContinuousBatchingEngine

        cfg, m = self._tiny()
        rng = np.random.default_rng(2)
        base = list(rng.integers(1, cfg.vocab_size, 32))
        eng = ContinuousBatchingEngine(m, max_batch_size=1,
                                       max_seq_len=256,
                                       enable_prefix_caching=True)
        rids = [eng.add_request(base + [5, 6], 8),
                eng.add_request(base + [9], 8)]
        res = eng.run()
        assert eng.prefix_hits == 1 and eng.prefix_tokens_reused == 32
        for r in rids:
            assert len(res[r]) == 8
            assert all(0 <= t < cfg.vocab_size for t in res[r])
