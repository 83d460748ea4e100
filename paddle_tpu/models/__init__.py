"""Model zoo for the north-star workloads (BASELINE.json configs):
BERT (MLM fine-tune), Llama-3 (pretraining flagship), MoE (DeepSeek/Qwen2
style), DiT (diffusion transformer). These play the role PaddleNLP/PaddleMIX
models play for the reference (SURVEY.md §1 model-zoo note)."""
from . import bert  # noqa: F401


def __getattr__(name):
    import importlib
    if name in ("llama", "llama_pipe", "moe", "dit", "gpt", "serving",
                "speculative", "generation", "ernie", "nemotron_h"):
        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(name)
