"""chip_smoke.py's phases, driven at `LlamaConfig.tiny()` on the CPU,
and the seams the smoke stands on: the chip gate, the compile-cache
placement, the healed-failure detector, kernels under a mesh.

The kernel-presence checks are off here (`expect_kernels=False`):
interpret mode is the CPU's path, so a CPU lowering holds no Mosaic
custom call. The chip run is `python chip_smoke.py` through the tool.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.distributed as dist
from paddle_tpu import device
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.utils.faults import FaultInjector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the smoke's traffic shape at a CPU size: request 0 and the last share
# the 512-token prefix (the prefix cache's 32 pages of 16), the last
# has the largest budget, 6 requests for 4 slots
TINY_TABLE = ((True, 535, 6), (False, 41, 9), (False, 77, 8),
              (False, 23, 12), (False, 61, 7), (True, 601, 14))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "_chip_smoke_under_test", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(max_len=1024):
    return dataclasses.replace(LlamaConfig.tiny(), dtype="float32",
                               max_position_embeddings=max_len)


def _serve(smoke, **kw):
    args = dict(table=TINY_TABLE, max_seq_len=1024, slots=4,
                expect_kernels=False)
    args.update(kw)
    return smoke.phase_serve(_tiny(), **args)


class TestTrainPhase:
    def test_one_device(self, smoke):
        out = smoke.phase_train(_tiny(256), batch=2, seq=128, steps=3,
                                expect_kernels=False)
        assert out["loss_last"] < out["loss_first"]
        assert out["mosaic_kernels"] == {}       # the CPU's lowering
        assert out["steps"] == 4     # 3 synced + the queued sync check

    def test_missing_kernel_fails(self, smoke):
        with pytest.raises(smoke.SmokeFailure, match="flash_fwd"):
            smoke.phase_train(_tiny(256), batch=2, seq=128, steps=2)

    def test_sharded_over_a_mesh(self, smoke):
        out = smoke.phase_train(
            _tiny(256), batch=4, seq=128, steps=2,
            mesh_axes={"sharding": 2, "mp": 2}, expect_kernels=False)
        assert out["mesh"] == {"sharding": 2, "mp": 2}
        assert out["loss_last"] < out["loss_first"]


class TestServePhase:
    def test_two_passes_and_logits(self, smoke):
        out = _serve(smoke, logits_tolerance=1e-3, logits_prompt_len=45,
                     logits_steps=4)
        assert out["second_pass_compiles"] == 0
        assert out["prefix_hits"] >= 1          # the suffix prefill ran
        assert {"ragged", "decode"} <= set(out["compiles"])
        assert out["logits"]["max_abs_err_over_ref_std"] <= 1e-3
        assert out["logits"]["argmax_agree"] == 4
        assert out["ledger"]["kv_pool"] > 0

    def test_logits_outside_tolerance_fails(self, smoke):
        with pytest.raises(smoke.SmokeFailure, match="tolerance"):
            _serve(smoke, second_pass=False, logits_tolerance=-1.0,
                   logits_prompt_len=45, logits_steps=2)

    def test_tp_replicas(self, smoke):
        out = _serve(smoke, num_replicas=2, tp=2, second_pass=False)
        assert out["replicas"] == 2 and out["tp"] == 2

    def test_missing_kernel_fails(self, smoke):
        with pytest.raises(smoke.SmokeFailure,
                           match="ragged_paged_attention"):
            _serve(smoke, second_pass=False, expect_kernels=True)

    def test_isolated_prefill_failure_fails_the_phase(self, smoke):
        """The engine turns an exception at the prefill site into ONE
        failed request and keeps serving; the smoke must not."""
        with FaultInjector(seed=0) as fi:
            fi.arm("serving.prefill", nth=2)
            with pytest.raises(smoke.SmokeFailure) as err:
                _serve(smoke, second_pass=False)
        assert fi.trips("serving.prefill") == 1
        msg = str(err.value)
        assert "healed" in msg and "num_failures" in msg
        # the first exception with its traceback, not a summary
        assert "Traceback" in msg and "FaultError" in msg

    def test_replica_step_failure_fails_the_phase(self, smoke):
        """The router turns an exception in a replica's step into
        degraded -> dead -> restart; the smoke must not."""
        with FaultInjector(seed=0) as fi:
            fi.arm("router.step", nth=3)
            with pytest.raises(smoke.SmokeFailure) as err:
                _serve(smoke, second_pass=False)
        msg = str(err.value)
        assert "replica 0 raised" in msg
        assert "Traceback" in msg and "FaultError" in msg

    def test_retried_decode_fails_the_phase(self, smoke):
        with FaultInjector(seed=0) as fi:
            fi.arm("serving.decode", nth=4)
            with pytest.raises(smoke.SmokeFailure,
                               match="num_decode_retries"):
                _serve(smoke, second_pass=False)


class TestGate:
    def test_refuses_a_cpu(self):
        with pytest.raises(RuntimeError, match="TPU only") as err:
            device.require_tpu()
        assert "CpuDevice" in str(err.value)     # names what it found

    def test_describes_what_jax_found(self):
        info = device.describe_devices()
        assert info["platform"] == "cpu"
        assert info["count"] == len(jax.devices())
        assert info["jax"] == jax.__version__

    def test_no_silent_landing_on_the_cpu(self):
        from paddle_tpu.core.tensor import _resolve_device
        with pytest.raises(RuntimeError):
            device.set_device("tpu")
        with pytest.raises(RuntimeError, match="accelerator"):
            _resolve_device("tpu:0")
        assert _resolve_device("cpu").platform == "cpu"

    def test_spawn_refuses_on_a_tpu_host(self, monkeypatch):
        from paddle_tpu.distributed import parallel

        class FakeTpu:
            platform = "tpu"
        monkeypatch.setattr(parallel.jax, "devices",
                            lambda *a: [FakeTpu()])
        with pytest.raises(RuntimeError, match="already holds"):
            parallel.spawn(print, nprocs=2)


class TestResultLine:
    def test_last_line_is_the_drivers_object_and_nothing_else(
            self, smoke, monkeypatch, capsys):
        """The driver parses the LAST line of stdout and refuses any key
        beyond `ok` and `device{platform, kind, count}`; what the phases
        measured goes on the `[summary]` line before it."""
        info = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "jax": "0", "jaxlib": "0", "libtpu": "0"}
        monkeypatch.setattr(smoke, "require_tpu", lambda: info)
        monkeypatch.setattr(smoke, "enable_compile_cache", lambda: "/c")
        monkeypatch.setattr(smoke, "phase_train", lambda *a, **k: {"t": 1})
        monkeypatch.setattr(smoke, "phase_serve", lambda *a, **k: {"s": 1})
        assert smoke.main() == 0
        lines = capsys.readouterr().out.strip().splitlines()
        assert json.loads(lines[-1]) == {
            "ok": True, "device": {"platform": "tpu",
                                   "kind": "TPU v5 lite", "count": 1}}
        tag, _, summary = lines[-2].partition(" ")
        assert tag == "[summary]" and summary.endswith('"claim": null}')
        assert json.loads(summary)["phases"] == {"train": {"t": 1},
                                                 "serve": {"s": 1}}
        assert any("NOT RUN" in ln and "train_4" in ln for ln in lines)


class TestCompileCache:
    def test_env_from_outside_is_left_alone(self, monkeypatch, tmp_path):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert device.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_path_in_the_checkout(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            first = device.enable_compile_cache()
            second = device.enable_compile_cache()
            assert first == second == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        # git would not commit it
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


class TestKernelSeams:
    def test_xla_reference_wins_over_force_mosaic(self, monkeypatch):
        from paddle_tpu import ops
        monkeypatch.setenv("PDT_FORCE_MOSAIC", "1")
        assert ops.on_tpu()
        with ops.xla_reference():
            assert not ops.on_tpu()
            with ops.xla_reference():
                assert not ops.on_tpu()
            assert not ops.on_tpu()
        assert ops.on_tpu()

    def test_mosaic_kernels_reads_lowered_text(self):
        from paddle_tpu.ops import mosaic_kernels
        text = (
            '%0 = stablehlo.custom_call @tpu_custom_call(%a) '
            '{backend_config = "x", kernel_name = "flash_fwd", a = 1}\n'
            '%1 = stablehlo.custom_call @tpu_custom_call(%b) '
            '{kernel_name = "flash_fwd"}\n'
            '%2 = stablehlo.custom_call @Sharding(%c) {x = "y"}\n'
            '%3 = stablehlo.custom_call @tpu_custom_call(%d) '
            '{kernel_name = "rms_norm_fwd"}\n')
        assert mosaic_kernels(text) == {"flash_fwd": 2,
                                        "rms_norm_fwd": 1}
        # a CPU lowering of a kernel in interpret mode holds none
        from paddle_tpu.ops.norm_kernels import rms_norm_values
        low = jax.jit(rms_norm_values).lower(jnp.ones((8, 128)),
                                             jnp.ones(128))
        assert mosaic_kernels(low.as_text()) == {}

    def test_kernels_under_a_mesh_match_one_device(self):
        """Mosaic cannot partition a kernel, so under a mesh the flash
        and norm kernels run per shard (`mesh.shard_kernel`); values and
        gradients must not change. Interpret mode here — the TPU compile
        of the same programs is tests/test_tpu_compile.py's."""
        from paddle_tpu.ops.flash_attention import flash_attention_values
        from paddle_tpu.ops.norm_kernels import (layer_norm_values,
                                                 rms_norm_values)
        rng = np.random.default_rng(0)

        def arr(*shape):
            return jnp.asarray(rng.standard_normal(shape), jnp.float32)
        x, w, b = arr(4, 256, 128), arr(128), arr(128)
        q, k, v = arr(4, 256, 8, 32), arr(4, 256, 4, 32), \
            arr(4, 256, 4, 32)

        def loss(x, w, b, q, k, v):
            return (jnp.sin(rms_norm_values(x, w)).sum()
                    + jnp.sin(layer_norm_values(x, w, b)).sum()
                    + jnp.sin(flash_attention_values(
                        q, k, v, causal=True)).sum())

        def run():
            return jax.jit(jax.value_and_grad(
                loss, argnums=tuple(range(6))))(x, w, b, q, k, v)
        ref_loss, ref_grads = run()
        with dist.use_mesh(dist.create_mesh(sharding=2, mp=2)):
            got_loss, got_grads = run()
        np.testing.assert_allclose(got_loss, ref_loss, rtol=1e-5)
        for g, r in zip(got_grads, ref_grads):
            np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-3)
        # q's gradient came back split over batch and heads: the kernel
        # really ran per shard
        assert got_grads[3].sharding.spec == \
            jax.sharding.PartitionSpec("sharding", None, "mp")
