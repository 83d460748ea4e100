"""Offline Mosaic tier: compile every Pallas kernel for a TPU v5e
WITHOUT a chip (VERDICT r4 #8).

What this tier proves under the installed jax 0.9.0 / libtpu 0.0.34.
The Pallas->Mosaic LOWERING (what `jax.export(..., platforms=["tpu"])`
alone would run, and all this tier did before PR 21) only builds the
kernel's MLIR and checks its block shapes in Python: it catches a block
whose last two dimensions break the (8, 128) rule — the LoRA epilogue's
first BlockSpec — but not the class of error that broke BENCH_r02,
"XLA layout does not match Mosaic layout", because the layout and
vector passes run inside libtpu when XLA COMPILES the custom call. So
this tier compiles: libtpu builds a compile-only client for a `v5e:2x2`
topology description (`jax.experimental.topologies`), and every
program is lowered against one of its devices and compiled — the same
Mosaic and XLA passes the chip machine runs, with no chip. Programs
that span devices (kernels under a mesh, the TP shard_map kernel)
compile against the topology's 2x2 mesh.

Execution still needs silicon: a kernel that compiles can still fault
or be wrong at run time. tests/test_tpu_compile.py is the execute gate.

PDT_FORCE_MOSAIC=1 flips every kernel's `on_tpu()` gate so the
non-interpret Pallas path is traced while the process runs on CPU.
The module skips when libtpu cannot describe the topology.

Shapes mirror tests/test_tpu_compile.py.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH_B, BENCH_S, BENCH_H, BENCH_HK, BENCH_D = 8, 2048, 16, 8, 64
BENCH_HIDDEN = 1024
BENCH_ROWS = BENCH_B * BENCH_S
# head_dim 128 as GQA group 4 and as MHA (ROADMAP Queue 2's widths)
HEADS_128 = [(32, 8, 128), (16, 16, 128)]


@pytest.fixture(autouse=True)
def _force_mosaic(monkeypatch):
    monkeypatch.setenv("PDT_FORCE_MOSAIC", "1")


@functools.lru_cache(maxsize=None)
def _v5e():
    """The four devices of a compile-only v5e 2x2 topology."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc("v5e:2x2", "tpu").devices
    except Exception as e:      # no libtpu / it cannot start here
        pytest.skip(f"libtpu cannot describe a v5e topology: {e}")


def _lower(fn, *args, mesh=None, sharding=None):
    """Lower for a v5e device and COMPILE: a BlockSpec the lowering
    refuses and a layout Mosaic or XLA refuses both raise here. Does
    NOT execute. `mesh` (a jax Mesh of `_v5e()` devices) is made the
    active training mesh and `sharding` maps each argument to its
    sharding on it; without one no mesh is active, whatever an earlier
    test left set. Returns {kernel: count} of the Mosaic kernels in
    the program."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.mesh import ProcessMesh
    from paddle_tpu.ops import mosaic_kernels
    one = jax.sharding.SingleDeviceSharding(_v5e()[0])
    shardings = sharding or [one] * len(args)
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
             for a, s in zip(args, shardings)]
    with dist.use_mesh(ProcessMesh(mesh) if mesh is not None else None):
        lowered = jax.jit(fn).lower(*avals)
        lowered.compile()
    return mosaic_kernels(lowered.as_text())


class TestNormLowering:
    def test_rms_norm_fwd_bwd(self):
        from paddle_tpu.ops.norm_kernels import rms_norm_values

        x = jnp.zeros((BENCH_ROWS, BENCH_HIDDEN), jnp.bfloat16)
        w = jnp.ones((BENCH_HIDDEN,), jnp.bfloat16)
        _lower(rms_norm_values, x, w)

        def loss(x, w):
            return rms_norm_values(x, w).astype(jnp.float32).sum()

        _lower(jax.grad(loss, argnums=(0, 1)), x, w)

    def test_layer_norm_fwd_bwd(self):
        from paddle_tpu.ops.norm_kernels import layer_norm_values

        x = jnp.zeros((BENCH_ROWS, BENCH_HIDDEN), jnp.bfloat16)
        w = jnp.ones((BENCH_HIDDEN,), jnp.bfloat16)
        b = jnp.zeros((BENCH_HIDDEN,), jnp.bfloat16)

        def loss(x, w, b):
            return layer_norm_values(x, w, b).astype(jnp.float32).sum()

        _lower(layer_norm_values, x, w, b)
        _lower(jax.grad(loss, argnums=(0, 1, 2)), x, w, b)


class TestFlashLowering:
    def _qkv(self, heads=(BENCH_H, BENCH_HK, BENCH_D), batch=BENCH_B):
        h, hk, d = heads
        q = jnp.zeros((batch, BENCH_S, h, d), jnp.bfloat16)
        k = jnp.zeros((batch, BENCH_S, hk, d), jnp.bfloat16)
        return q, k, k

    @pytest.mark.parametrize("heads", HEADS_128)
    def test_fwd_bwd_head_dim_128(self, heads):
        from paddle_tpu.ops.flash_attention import flash_attention_values

        def loss(q, k, v):
            return flash_attention_values(
                q, k, v, causal=True).astype(jnp.float32).sum()

        _lower(jax.grad(loss, argnums=(0, 1, 2)),
               *self._qkv(heads, batch=2))

    @pytest.mark.parametrize("kw", [dict(causal=False), dict(causal=True),
                                    dict(causal=True, window_size=512)])
    def test_fwd_bwd(self, kw):
        from paddle_tpu.ops.flash_attention import flash_attention_values

        q, k, v = self._qkv()
        _lower(lambda q, k, v: flash_attention_values(q, k, v, **kw),
               q, k, v)

        def loss(q, k, v):
            return flash_attention_values(
                q, k, v, **kw).astype(jnp.float32).sum()

        _lower(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


class TestVarlenLowering:
    def test_fwd_bwd_packed(self):
        from paddle_tpu.ops.flash_varlen import (
            flash_attention_varlen_values)

        q = jnp.zeros((BENCH_B, BENCH_S, BENCH_H, BENCH_D), jnp.bfloat16)
        k = jnp.zeros((BENCH_B, BENCH_S, BENCH_HK, BENCH_D), jnp.bfloat16)
        seg = jnp.zeros((BENCH_B, BENCH_S), jnp.int32)

        def loss(q, k, v):
            return flash_attention_varlen_values(
                q, k, v, seg, seg, causal=True).astype(jnp.float32).sum()

        _lower(lambda q, k, v: flash_attention_varlen_values(
            q, k, v, seg, seg, causal=True), q, k, k)
        _lower(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)


class TestRopeLowering:
    def test_fwd_bwd(self):
        from paddle_tpu.ops.rope import rope_values

        x = jnp.zeros((BENCH_B, BENCH_S, BENCH_H, BENCH_D), jnp.bfloat16)
        cos = jnp.zeros((BENCH_S, BENCH_D // 2), jnp.float32)
        sin = jnp.zeros((BENCH_S, BENCH_D // 2), jnp.float32)
        _lower(rope_values, x, cos, sin)

        def loss(x, cos, sin):
            return rope_values(x, cos, sin).astype(jnp.float32).sum()

        _lower(jax.grad(loss), x, cos, sin)


class TestPagedAttentionLowering:
    @pytest.mark.parametrize("window", [None, 64])
    def test_decode(self, window):
        from paddle_tpu.ops.paged_attention import paged_attention_values

        b, pages, page_size = 8, 64, 16
        q = jnp.zeros((b, BENCH_H, BENCH_D), jnp.bfloat16)
        kp = jnp.zeros((pages, page_size, BENCH_HK * BENCH_D), jnp.bfloat16)
        ctx = jnp.full((b,), 100, jnp.int32)
        bt = jnp.zeros((b, 8), jnp.int32)
        _lower(lambda q, kp, vp: paged_attention_values(
            q, kp, vp, ctx, bt, window=window), q, kp, kp)


class TestRaggedPagedAttentionLowering:
    """ISSUE 6: the mixed prefill+decode grid — (block_q*G, D) q tiles,
    scalar-prefetched descriptors, trash-page index_map routing — must
    survive the Mosaic pass at bench shapes, windowed and not, and at
    the decode form (block_q=1)."""

    @pytest.mark.parametrize("window", [None, 256])
    def test_mixed_batch(self, window):
        from paddle_tpu.ops.ragged_paged_attention import (
            pack_ragged_starts, ragged_paged_attention_values)

        pages, page_size = 512, 16
        ql = np.array([512, 512, 1, 1, 1, 1], np.int32)
        cl = np.array([512, 512, 900, 800, 700, 600], np.int32)
        qs, total = pack_ragged_starts(ql, block_q=8)
        q = jnp.zeros((total, BENCH_H, BENCH_D), jnp.bfloat16)
        kp = jnp.zeros((pages, page_size, BENCH_HK * BENCH_D),
                       jnp.bfloat16)
        bt = jnp.zeros((len(ql), 64), jnp.int32)
        _lower(lambda q, kp, vp: ragged_paged_attention_values(
            q, kp, vp, qs, ql, cl, bt, window=window, block_q=8),
            q, kp, kp)

    def test_decode_block_q1(self):
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values

        b, pages, page_size = 8, 64, 16
        qs = np.arange(b, dtype=np.int32)
        ql = np.ones(b, np.int32)
        cl = np.full(b, 100, np.int32)
        q = jnp.zeros((b, BENCH_H, BENCH_D), jnp.bfloat16)
        kp = jnp.zeros((pages, page_size, BENCH_HK * BENCH_D),
                       jnp.bfloat16)
        bt = jnp.zeros((b, 8), jnp.int32)
        _lower(lambda q, kp, vp: ragged_paged_attention_values(
            q, kp, vp, qs, ql, cl, bt, block_q=1), q, kp, kp)

    @pytest.mark.parametrize("heads", HEADS_128)
    @pytest.mark.parametrize("block_q", [8, 1])
    def test_head_dim_128(self, block_q, heads):
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values

        h, hk, d = heads
        b, pps, page_size = 8, 128, 16
        q = jnp.zeros((b * block_q, h, d), jnp.bfloat16)
        kp = jnp.zeros((b * pps + 1, page_size, hk * d), jnp.bfloat16)
        i32 = jnp.zeros((b,), jnp.int32)
        bt = jnp.zeros((b, pps), jnp.int32)
        _lower(lambda q, kp, vp, qs, ql, cl, bt:
               ragged_paged_attention_values(q, kp, vp, qs, ql, cl, bt,
                                             block_q=block_q),
               q, kp, kp, i32, i32, i32, bt)

    @pytest.mark.parametrize("heads", [(1, 64), (2, 16), (3, 64)])
    @pytest.mark.parametrize("block_q", [8, 1])
    def test_row_no_whole_number_of_tiles(self, block_q, heads):
        """A stored row (HK*D lanes) narrower than a 128-lane tile, or
        ragged against it, cannot be sliced out of HBM by a DMA: the
        kernel's entry pads such pools (toy widths, a head sharded
        thinner than a tile)."""
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values

        hk, d = heads
        b, pps, page_size = 8, 16, 16
        q = jnp.zeros((b * block_q, 2 * hk, d), jnp.bfloat16)
        kp = jnp.zeros((b * pps + 1, page_size, hk * d), jnp.bfloat16)
        i32 = jnp.zeros((b,), jnp.int32)
        bt = jnp.zeros((b, pps), jnp.int32)
        _lower(lambda q, kp, vp, qs, ql, cl, bt:
               ragged_paged_attention_values(q, kp, vp, qs, ql, cl, bt,
                                             block_q=block_q),
               q, kp, kp, i32, i32, i32, bt)

    @pytest.mark.parametrize("block_q", [8, 1])
    def test_tp_shard_map(self, block_q):
        """ISSUE 12: under a TP replica the kernel runs per head shard
        (`_ragged_tp_shard_map`); a bare kernel in a two-device program
        does not lower ("Mosaic kernels cannot be automatically
        partitioned")."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values

        mesh = Mesh(np.asarray(_v5e()[:2]), ("tp",))
        b, pps, page_size = 8, 128, 16
        q = jnp.zeros((b * block_q, 32, BENCH_D), jnp.bfloat16)
        kp = jnp.zeros((b * pps + 1, page_size, 8 * BENCH_D),
                       jnp.bfloat16)
        i32 = jnp.zeros((b,), jnp.int32)
        bt = jnp.zeros((b, pps), jnp.int32)
        rep = NamedSharding(mesh, P())
        pool = NamedSharding(mesh, P(None, None, "tp"))
        _lower(lambda q, kp, vp, qs, ql, cl, bt:
               ragged_paged_attention_values(
                   q, kp, vp, qs, ql, cl, bt, block_q=block_q,
                   tp=(mesh, "tp")),
               q, kp, kp, i32, i32, i32, bt,
               sharding=[NamedSharding(mesh, P(None, "tp", None)), pool,
                         pool, rep, rep, rep, rep])

    @pytest.mark.parametrize("block_q", [8, 1])
    def test_quantized_pages(self, block_q):
        """ISSUE 15: int8 page pools + (P, 1, page_size) scale blocks
        (the dequant-in-flight inputs) must survive the Mosaic pass at
        bench shapes, mixed and decode forms."""
        from paddle_tpu.ops.ragged_paged_attention import (
            pack_ragged_starts, ragged_paged_attention_values)

        pages, page_size = 256, 16
        if block_q == 8:
            ql = np.array([512, 512, 1, 1], np.int32)
            cl = np.array([512, 512, 900, 800], np.int32)
        else:
            ql = np.ones(4, np.int32)
            cl = np.array([100, 90, 80, 70], np.int32)
        qs, total = pack_ragged_starts(ql, block_q=block_q)
        q = jnp.zeros((total, BENCH_H, BENCH_D), jnp.bfloat16)
        kp = jnp.zeros((pages, page_size, BENCH_HK * BENCH_D), jnp.int8)
        ks = jnp.zeros((pages, page_size), jnp.float32)
        bt = jnp.zeros((len(ql), 64), jnp.int32)
        _lower(lambda q, kp, vp, ks, vs: ragged_paged_attention_values(
            q, kp, vp, qs, ql, cl, bt, block_q=block_q,
            k_scale=ks, v_scale=vs), q, kp, kp, ks, ks)


    # the benchmark's cells (ISSUE 26, 30): (Q heads, KV heads, slots,
    # rows, table columns, block_q) — InternLM2-1.8B has G = 2 and 128
    # columns of 16 tokens (max_seq_len 2048), Mistral-7B G = 4 and 64
    # columns, both 8 KV heads of 128 and 32 slots; the hybrid's one
    # attention block G = 16 on 2 KV heads, 64 slots of 512 columns
    CELL_SHAPES = {
        "batch.decode": (16, 8, 32, 32, 128, 1),
        "batch.admit512": (16, 8, 32, 512, 128, 8),
        "chat.decode": (32, 8, 32, 32, 64, 1),
        "chat.admit256": (32, 8, 32, 256, 64, 8),
        "reasoning.decode": (32, 2, 64, 64, 512, 1),
        "reasoning.admit1024": (32, 2, 64, 1024, 512, 8),
    }

    @pytest.mark.parametrize("variant", ["bf16", "int8", "window", "tp2"])
    @pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
    def test_cell_shapes(self, shape, variant):
        """ISSUE 26: the in-kernel loop over KV blocks — HBM pools,
        per-page DMAs indexed by the prefetched table, a dynamic trip
        count — at the real shapes of the three cells' decode and
        admission programs with the cells' own dtype pair (bf16 q on
        bf16 pools: bf16 MXU operands, ISSUE 30), on int8 pools, with a
        window, and per shard of a tp = 2 replica."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values

        h, hk, slots, rows, pps, block_q = self.CELL_SHAPES[shape]
        d, page_size = 128, 16
        pages = slots * pps + 1
        q = jnp.zeros((rows, h, d), jnp.bfloat16)
        kp = jnp.zeros((pages, page_size, hk * d),
                       jnp.int8 if variant == "int8" else jnp.bfloat16)
        i32 = jnp.zeros((slots,), jnp.int32)
        bt = jnp.zeros((slots, pps), jnp.int32)
        kw, extra, sharding, mesh = {}, (), None, None
        if variant == "window":
            kw["window"] = 512
        if variant == "int8":
            extra = (jnp.zeros((pages, page_size), jnp.float32),) * 2
        if variant == "tp2":
            mesh = Mesh(np.asarray(_v5e()[:2]), ("tp",))
            kw["tp"] = (mesh, "tp")
            rep = NamedSharding(mesh, P())
            pool = NamedSharding(mesh, P(None, None, "tp"))
            sharding = [NamedSharding(mesh, P(None, "tp", None)), pool,
                        pool, rep, rep, rep, rep]

        def fn(q, kp, vp, qs, ql, cl, bt, *scales):
            if scales:
                kw.update(k_scale=scales[0], v_scale=scales[1])
            return ragged_paged_attention_values(
                q, kp, vp, qs, ql, cl, bt, block_q=block_q, **kw)

        kernels = _lower(fn, q, kp, kp, i32, i32, i32, bt, *extra,
                         sharding=sharding)
        assert kernels.get("ragged_paged_attention") == 1, kernels


class TestPoolWriteInPlace:
    """ISSUE 28: a page pool is stored token-major, (P, page_size,
    HK*D), so that the layout XLA gives the K/V row scatter IS the
    stored one. Stored head-major every layer of every program held,
    for K and for V, one whole-pool `copy` in front of the scatter and
    one behind it (40 of a decode step's 51 ms in the batch cell). The
    static proof that the write engages in place: one layer's write +
    ragged attention call, pools donated, compiled for the v5e at the
    three cells' pool shapes — no `copy` of a pool-shaped array, and
    less than one pool of temporaries."""

    # (KV heads, head_dim, pool dtype, slots, table columns): the batch
    # cell (InternLM2, chat differs in columns only), the hybrid's
    # attention block, the int8 engine's pools with their scale pools
    POOLS = {
        "hk8-d128-bf16": (8, 128, jnp.bfloat16, 32, 128),
        "hk2-d128-bf16": (2, 128, jnp.bfloat16, 64, 512),
        "hk8-d128-int8": (8, 128, jnp.int8, 32, 128),
    }

    @pytest.mark.parametrize("block_q", [1, 8])
    @pytest.mark.parametrize("pool", sorted(POOLS))
    def test_write_then_attention_copies_no_pool(self, pool, block_q):
        import re
        from paddle_tpu.ops.ragged_paged_attention import (
            ragged_paged_attention_values, ragged_scatter_quantized,
            ragged_scatter_values)

        hk, d, dt, slots, pps = self.POOLS[pool]
        page_size, g = 16, 2
        pages = slots * pps + 1
        rows = slots if block_q == 1 else 512
        quantized = dt == jnp.int8

        def layer(kp, vp, ks, vs, q, k, v, bt, seq, pos, qs, ql, cl):
            kw = {}
            if quantized:
                kp, vp, ks, vs = ragged_scatter_quantized(
                    kp, vp, ks, vs, k, v, bt, seq, pos)
                kw = dict(k_scale=ks, v_scale=vs)
            else:
                kp, vp = ragged_scatter_values(kp, vp, k, v, bt, seq, pos)
            out = ragged_paged_attention_values(
                q, kp, vp, qs, ql, cl, bt, block_q=block_q, **kw)
            return out, kp, vp, ks, vs

        one = jax.sharding.SingleDeviceSharding(_v5e()[0])

        def aval(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        kp = aval((pages, page_size, hk * d), dt)
        ks = aval((pages, page_size), jnp.float32)
        kv = aval((rows, hk, d), jnp.bfloat16)
        row, slot = aval((rows,), jnp.int32), aval((slots,), jnp.int32)
        compiled = jax.jit(layer, donate_argnums=(0, 1, 2, 3)).lower(
            kp, kp, ks, ks, aval((rows, hk * g, d), jnp.bfloat16), kv, kv,
            aval((slots, pps), jnp.int32), row, row, slot, slot,
            slot).compile()
        pool_shape = re.escape(f"[{pages},{page_size},{hk * d}]")
        copies = [line.strip()[:160]
                  for line in compiled.as_text().splitlines()
                  if re.search(r"\bcopy(-start)?\(", line)
                  and re.search(pool_shape, line)]
        assert not copies, copies
        pool_bytes = pages * page_size * hk * d * jnp.dtype(dt).itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


class TestQuantMatmulLowering:
    """ISSUE 15: the fused dequant-matmul epilogue — int8 weight tiles
    widened in VMEM, per-out-channel scale applied to the f32
    accumulator on the last K step — at decode (M=8) and prefill
    (M=1024) shapes."""

    @pytest.mark.parametrize("m", [8, 1024])
    def test_int8_epilogue(self, m):
        from paddle_tpu.ops.quant_matmul import (dequant_matmul_values,
                                                 quantize_weight_values)
        k, n = 1024, 4096
        qw, sc = quantize_weight_values(jnp.zeros((k, n)), "int8")
        x = jnp.zeros((m, k), jnp.bfloat16)
        _lower(lambda x, qw, sc: dequant_matmul_values(x, qw, sc),
               x, qw, sc)

    # the block-diffusion cell (ISSUE 31): 32 Q on 4 KV heads, 64 slots
    # of 256 columns; a pass is 4 rows a slot at block_q 4, an admission
    # 512 rows at block_q 8, both under the mask by blocks of 4
    BLOCK_CELL_SHAPES = {
        "generate.pass": (32, 4, 64, 256, 256, 4),
        "generate.admit512": (32, 4, 64, 512, 256, 8),
    }

    @pytest.mark.parametrize("shape", sorted(BLOCK_CELL_SHAPES))
    def test_block_cell_shapes(self, shape):
        """`diffusion_block` is a static parameter of the one kernel:
        the frontier of a row is the end of its block."""
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values

        h, hk, slots, rows, pps, block_q = self.BLOCK_CELL_SHAPES[shape]
        d, page_size = 128, 16
        q = jnp.zeros((rows, h, d), jnp.bfloat16)
        kp = jnp.zeros((slots * pps + 1, page_size, hk * d), jnp.bfloat16)
        i32 = jnp.zeros((slots,), jnp.int32)
        bt = jnp.zeros((slots, pps), jnp.int32)
        kernels = _lower(
            lambda q, kp, vp, qs, ql, cl, bt: ragged_paged_attention_values(
                q, kp, vp, qs, ql, cl, bt, block_q=block_q,
                diffusion_block=4), q, kp, kp, i32, i32, i32, bt)
        assert kernels.get("ragged_paged_attention") == 1, kernels


class TestGroupedMatmulLowering:
    def test_grouped(self):
        from paddle_tpu.ops.grouped_matmul import grouped_matmul_values

        e, n = 8, 2048
        x = jnp.zeros((n, BENCH_HIDDEN), jnp.bfloat16)
        w = jnp.zeros((e, BENCH_HIDDEN, BENCH_HIDDEN), jnp.bfloat16)
        sizes = jnp.full((e,), n // e, jnp.int32)
        # groups in multiples of 128 rows: the Pallas kernel, not XLA's
        # ragged_dot (whose own TPU kernel refuses bf16 under this
        # suite's global "highest" matmul precision — "Bad lhs type")
        assert _lower(
            lambda x, w, sizes: grouped_matmul_values(x, w, sizes, 128),
            x, w, sizes) == {"grouped_matmul": 1}

    @pytest.mark.parametrize("rows,k,n", [
        (64, 1024, 2688), (64, 2688, 1024),        # a decode step
        (1024, 1024, 2688), (1024, 2688, 1024)])   # an admission
    def test_expert_layer_shapes(self, rows, k, n):
        """ISSUE 27: the latent expert layer of the reasoning cell: 128
        held experts of 512, 22 choices a row, groups padded to
        `row_block` (16 rows at decode, 64 at an admission), an expert's
        whole (K, N) a weight block."""
        from paddle_tpu.ops.grouped_matmul import (grouped_matmul_values,
                                                   row_block)
        held, top_k = 128, 22
        bm = row_block(rows * top_k / 512)
        m = -(-(rows * top_k + held * (bm - 1)) // bm) * bm
        x = jnp.zeros((m, k), jnp.bfloat16)
        w = jnp.zeros((held, k, n), jnp.bfloat16)
        sizes = jnp.full((held,), bm, jnp.int32)
        assert _lower(
            lambda x, w, sizes: grouped_matmul_values(x, w, sizes, bm),
            x, w, sizes) == {"grouped_matmul": 1}

    @pytest.mark.parametrize("rows,k,n", [
        (256, 2048, 1536), (256, 768, 2048),       # a pass: 64 slots x 4
        (512, 2048, 1536), (512, 768, 2048),       # an admission
        (1024, 2048, 1536), (1024, 768, 2048)])    # two chunks of one
    def test_swiglu_expert_layer_shapes(self, rows, k, n):
        """ISSUE 31: the SwiGLU expert layer of the block-diffusion
        cell: all 128 experts held, 8 choices a row, gate and up one
        operand of 2 x 768 columns; groups padded to `row_block` (16
        rows at a pass, 32 and 64 at an admission)."""
        from paddle_tpu.ops.grouped_matmul import (grouped_matmul_values,
                                                   row_block)
        held, top_k = 128, 8
        bm = row_block(rows * top_k / held)
        m = -(-(rows * top_k + held * (bm - 1)) // bm) * bm
        x = jnp.zeros((m, k), jnp.bfloat16)
        w = jnp.zeros((held, k, n), jnp.bfloat16)
        sizes = jnp.full((held,), bm, jnp.int32)
        assert _lower(
            lambda x, w, sizes: grouped_matmul_values(x, w, sizes, bm),
            x, w, sizes) == {"grouped_matmul": 1}


    # ISSUE 32: the stationary grid holds the whole of K and an n block
    # of an expert's weights in VMEM, twice (the pipeline's two
    # buffers). (K, N, dtype) -> the n block the kernel derives.
    SERVED_WEIGHT_BLOCKS = [
        (2048, 1536, "bfloat16", 1536),     # generate: gate and up
        (768, 2048, "bfloat16", 2048),      # generate: down
        (1024, 2688, "bfloat16", 2688),     # reasoning: up
        (2688, 1024, "bfloat16", 1024),     # reasoning: down
        (1536, 2048, "bfloat16", 2048),     # d(lhs) of gate and up
        (2048, 1536, "float32", 768),       # float32 weights: half
        (8192, 8192, "bfloat16", 256),
    ]

    @pytest.mark.parametrize("k,n,dtype,want", SERVED_WEIGHT_BLOCKS)
    def test_weight_block_follows_k_n_and_dtype(self, k, n, dtype, want):
        """The largest 128-multiple dividing N whose two buffers fit the
        budget the kernel states, and the v5e compile takes it at a
        16-row and at a 64-row tile."""
        from paddle_tpu.ops import grouped_matmul as gm
        size = jnp.dtype(dtype).itemsize
        assert gm._weight_block_n(k, n, size) == want
        assert 2 * k * want * size <= gm.WEIGHT_VMEM_BUDGET
        assert all(2 * k * t * size > gm.WEIGHT_VMEM_BUDGET
                   for t in range(want + 128, n + 1, 128) if n % t == 0)
        for bm in (16, 64):
            x = jnp.zeros((8 * bm, k), dtype)
            w = jnp.zeros((4, k, n), dtype)
            sizes = jnp.full((4,), bm, jnp.int32)
            assert _lower(
                lambda x, w, sizes: gm.grouped_matmul_values(
                    x, w, sizes, bm), x, w, sizes) == {"grouped_matmul": 1}

    @pytest.mark.parametrize("rows,k,n", [
        (256, 2048, 1536), (256, 768, 2048), (512, 2048, 1536)])
    def test_swiglu_input_gradient_shapes(self, rows, k, n):
        """The custom vjp's d(lhs) at the served tile: the same kernel
        on the transposed weights, one `grouped_matmul` call."""
        from paddle_tpu.ops import grouped_matmul as gm
        held, top_k = 128, 8
        bm = gm.row_block(rows * top_k / held)
        m = -(-(rows * top_k + held * (bm - 1)) // bm) * bm
        dout = jnp.zeros((m, n), jnp.bfloat16)
        w = jnp.zeros((held, k, n), jnp.bfloat16)
        sizes = jnp.full((held,), bm, jnp.int32)
        assert _lower(
            lambda dout, w, sizes: gm._gmm(
                dout, jnp.swapaxes(w, 1, 2), sizes, bm),
            dout, w, sizes) == {"grouped_matmul": 1}


class TestLoraEpilogueLowering:
    """ISSUE 17: the per-token adapter epilogue at decode and prefill
    token counts. Its first BlockSpec — a (1, K) block of a (T, K)
    array — did not lower for the TPU at all (found in PR 21: this
    tier had no entry for it)."""

    @pytest.mark.parametrize("t", [8, 1504])
    def test_epilogue(self, t):
        from paddle_tpu.ops.lora_epilogue import lora_epilogue_values

        x = jnp.zeros((t, 2048), jnp.bfloat16)
        a = jnp.zeros((4, 2048, 16), jnp.bfloat16)
        b = jnp.zeros((4, 16, 2048), jnp.bfloat16)
        _lower(lora_epilogue_values, x, a, b,
               jnp.zeros((4,), jnp.float32), jnp.zeros((t,), jnp.int32))


class TestKernelsUnderAMesh:
    """The train step under `create_mesh(sharding=2, mp=2)` reaches the
    flash and norm kernels inside a four-device program; Mosaic cannot
    partition a kernel, so they run per shard (`mesh.shard_kernel`)."""

    def test_flash_and_rms_norm_fwd_bwd(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.flash_attention import flash_attention_values
        from paddle_tpu.ops.norm_kernels import rms_norm_values

        mesh = Mesh(np.asarray(_v5e()).reshape(2, 2), ("sharding", "mp"))
        q = jnp.zeros((4, BENCH_S, 32, 64), jnp.bfloat16)
        k = jnp.zeros((4, BENCH_S, 8, 64), jnp.bfloat16)
        x = jnp.zeros((4, BENCH_S, 2048), jnp.bfloat16)
        w = jnp.zeros((2048,), jnp.bfloat16)

        def loss(q, k, v, x, w):
            return (flash_attention_values(q, k, v, causal=True)
                    .astype(jnp.float32).sum()
                    + rms_norm_values(x, w).astype(jnp.float32).sum())

        heads = NamedSharding(mesh, P("sharding", None, "mp", None))
        found = _lower(
            jax.grad(loss, argnums=(0, 1, 2, 3, 4)), q, k, k, x, w,
            mesh=mesh, sharding=[heads, heads, heads,
                                 NamedSharding(mesh, P("sharding")),
                                 NamedSharding(mesh, P())])
        assert set(found) == {"flash_fwd", "flash_bwd_dq",
                              "flash_bwd_dkv", "rms_norm_fwd",
                              "rms_norm_bwd"}


class TestPageGroupPrograms:
    """ISSUE 35: the engine's two step programs for a model whose layers
    keep their keys and values differently (models/phi4flash.py: window
    layers, a full layer, layers that share it, Mamba-1 state), at the
    published widths (heads of 64 paired into the kernel's 128 lanes)
    and 8 of the 32 layers, compiled for a v5e as the engine builds
    them: a block table a page group, the attend-only call of a sharing
    layer, the sampled rows leaving after the full layer."""

    @pytest.mark.parametrize("program", ["decode", "admit"])
    def test_engine_programs_compile(self, program):
        from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                                 Phi4FlashForCausalLM)
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        from paddle_tpu.ops import mosaic_kernels
        one = jax.sharding.SingleDeviceSharding(_v5e()[0])
        box = {}

        def build():
            model = Phi4FlashForCausalLM(Phi4FlashConfig(
                num_hidden_layers=8, vocab_size=8192))
            model.to(dtype="bfloat16")
            model.eval()
            box["eng"] = ContinuousBatchingEngine(
                model, max_batch_size=8, max_seq_len=2048,
                prefill_chunk=256, prompt_pad=256, num_pages=257)
            return 0

        jax.eval_shape(build)          # shapes only: nothing is allocated
        eng = box["eng"]
        assert [g.name for g in eng._groups] == ["full", "w512"]

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

        def i32(*s):
            return jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)

        block_q, rows, bound = (1, eng.B, None) if program == "decode" \
            else (8, 256, 128)
        tree = jax.tree_util.tree_map
        lowered = eng._build_ragged_step(block_q, bound).lower(
            tree(sds, eng._pv()), tree(sds, eng._bv()),
            tree(sds, eng._cache()), i32(rows), i32(rows), i32(rows),
            i32(eng.B), i32(eng.B), i32(eng.B),
            tuple(i32(eng.B, eng.pps) for _ in eng._groups), i32(eng.B),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one))
        compiled = lowered.compile()
        # the attention kernel in the text (layers of one trace share a
        # function): with the window, and without it for the full layer
        # and the layer that shares it, which in an admission is a
        # third, one query a slot over the sampled rows alone; the
        # LayerNorm kernel
        kernels = mosaic_kernels(lowered.as_text())
        assert kernels.pop("ragged_paged_attention") \
            == (2 if program == "decode" else 3)
        assert set(kernels) == {"_ln_fwd_kernel"}
        mem = compiled.memory_analysis()
        # the pools and the state are donated and updated in place
        assert mem.alias_size_in_bytes >= 0.99 * sum(
            a.size * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(eng._cache()))
