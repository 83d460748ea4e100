"""paddle_tpu.ops — TPU kernel library (Pallas/Mosaic), the counterpart of the
reference's CUDA fused kernels («paddle/phi/kernels/fusion/» [U]).
Each op ships a Pallas fast path + XLA fallback with identical semantics."""
import contextlib as _contextlib
import os as _os
import re as _re

import jax as _jax

_xla_reference_depth = 0


def on_tpu() -> bool:
    """Shared TPU-detection gate for every Pallas fast path.

    PDT_FORCE_MOSAIC=1 reports True on any platform: the offline Mosaic
    tier (tests/test_mosaic_lowering.py) uses it to route every kernel
    down its non-interpret Pallas path while the process runs on CPU,
    then compiles for a TPU topology without a chip. Inside
    `xla_reference()` it reports False on any platform."""
    if _xla_reference_depth:
        return False
    if _os.environ.get("PDT_FORCE_MOSAIC") == "1":
        return True
    return _jax.devices()[0].platform == "tpu"


@_contextlib.contextmanager
def xla_reference():
    """Kernels off: whatever is TRACED inside this block takes every
    dispatcher's XLA reference path, on the chip too — how an oracle
    (chip_smoke.py's float32 logits check) gets the model's plain
    forward on a TPU. Trace-time only: a program jitted outside and
    called inside keeps the kernels it was compiled with."""
    global _xla_reference_depth
    _xla_reference_depth += 1
    try:
        yield
    finally:
        _xla_reference_depth -= 1


def mosaic_kernels(program_text: str) -> dict:
    """{kernel name: count} of the Mosaic custom calls in a lowered
    (StableHLO) program text — `jit(f).lower(...).as_text()`. An
    interpret-mode or XLA-reference lowering contains none, so this
    says afterwards which path a dispatcher took."""
    out: dict = {}
    for name in _re.findall(
            r'@tpu_custom_call\b[^\n]*?kernel_name = "([^"]+)"',
            program_text):
        out[name] = out.get(name, 0) + 1
    return out


def mxu_dot(a, b, dims, preferred_element_type=None):
    """dot_general pinned to DEFAULT precision for use INSIDE kernels.

    The kernels are bf16-MXU by design (bf16 x bf16 -> f32 accumulate is
    the native systolic-array mode). A global
    `jax_default_matmul_precision="highest"` — set e.g. by test harnesses
    for CPU-vs-NumPy parity — would otherwise leak into the traced kernel
    body as contract_precision<fp32> on bf16 operands, which Mosaic
    rejects ("Bad lhs type", seen live on v5e) and which would emulate
    fp32 matmul at 6x cost even where it compiled."""
    return _jax.lax.dot_general(
        a, b, dims, precision=_jax.lax.Precision.DEFAULT,
        preferred_element_type=preferred_element_type)


from . import flash_attention  # noqa: F401,E402
from . import flash_varlen  # noqa: F401,E402
from . import grouped_matmul  # noqa: F401,E402
from . import lora_epilogue  # noqa: F401,E402
from . import norm_kernels  # noqa: F401,E402
from . import paged_attention  # noqa: F401,E402
from . import quant_matmul  # noqa: F401,E402
from . import ragged_paged_attention  # noqa: F401,E402
from . import rope  # noqa: F401,E402
