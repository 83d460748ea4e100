"""The plain reference against the program's own model at tiny widths:
two independent implementations of the same equations."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.reference import llama_dense

SIZES = {"vocab_size": 97, "hidden_size": 64, "intermediate_size": 112,
         "num_hidden_layers": 3, "num_attention_heads": 4,
         "num_key_value_heads": 2, "max_position_embeddings": 128,
         "rms_norm_eps": 1e-5, "rope_theta": 1000000.0,
         "tie_word_embeddings": False, "torch_dtype": "float32"}
PROGRAM = {"module": "paddle_tpu.models.llama", "config_class": "LlamaConfig",
           "model_class": "LlamaForCausalLM",
           "fields": {k: k for k in SIZES if k != "torch_dtype"}
           | {"dtype": "torch_dtype"}}


def test_reference_agrees_with_the_programs_model():
    from paddle_tpu.autograd import no_grad
    from paddle_tpu.core.tensor import Tensor
    model, cfg = weights.build(PROGRAM, SIZES, seed=2**31 + 3)
    assert cfg.head_dim == 16
    ids = np.random.default_rng(0).integers(1, 97, 37)
    with no_grad(), jax.default_matmul_precision("highest"):
        got = np.asarray(model.forward(
            Tensor(jnp.asarray(ids, jnp.int32)[None]))._value[0])
    want = llama_dense.forward_logits(weights.named_values(model), SIZES,
                                      ids)
    assert want.shape == (37, 97)
    assert np.max(np.abs(got - want)) / np.std(want) < 1e-4


def test_weights_are_seeded_and_typed():
    a, _ = weights.build(PROGRAM, SIZES, seed=5)
    b, _ = weights.build(PROGRAM, SIZES, seed=5)
    c, _ = weights.build(PROGRAM, SIZES, seed=6)
    va, vb, vc = (weights.named_values(m) for m in (a, b, c))
    name = "model.layers.1.mlp.up_proj.weight"
    assert np.array_equal(np.asarray(va[name]), np.asarray(vb[name]))
    assert not np.array_equal(np.asarray(va[name]), np.asarray(vc[name]))
    # two layers of one shape are not the same numbers
    assert not np.array_equal(
        np.asarray(va[name]),
        np.asarray(va["model.layers.2.mlp.up_proj.weight"]))
    assert np.all(np.asarray(va["model.norm.weight"]) == 1.0)
    half = dict(SIZES, torch_dtype="bfloat16")
    m, _ = weights.build(PROGRAM, half, seed=5)
    assert {str(v.dtype) for v in weights.named_values(m).values()} \
        == {"bfloat16"}
    std = float(np.std(np.asarray(va[name], np.float32)))
    assert abs(std - (2.0 / (64 + 112)) ** 0.5) < 0.01
