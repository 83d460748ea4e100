"""The comparison that decides `correct` for a served model (the
`served_check` block of a cell's file), shown to fail: with the timed
path broken underneath a whole rehearsal of a run, and with the plain
reference a precision lower in the program's place."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["internlm2-1_8b.batch", "mistral-7b-v0_3.chat"]


def _rehearse(cell, capsys, monkeypatch, seed):
    """`run.py --dry` in this process (no look for a chip; the rest of a
    run as it is), and its last line."""
    from benchmark import run as harness
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", cell, "--seed", str(seed), "--seconds", "2",
        "--trace", "0", "--dry"])
    assert harness.main() == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    # each number compared, beside its limit: the last lines of standard
    # error, and the last key of the line
    assert list(line)[-1] == "compared"
    tail = out.err.strip().splitlines()[-len(line["compared"]):]
    assert [t.split()[1] for t in tail] == list(line["compared"])
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_a_token_altered_where_it_is_produced_is_not_correct(
        cell, capsys, monkeypatch):
    from paddle_tpu.models.serving import ContinuousBatchingEngine as Engine
    sound = _rehearse(cell, capsys, monkeypatch, 2**31 + 41)
    assert sound["correct"] is True
    assert sound["compared"]["served_gap_max"]["value"] \
        <= sound["compared"]["served_gap_max"]["limit"]
    commit = Engine._commit

    def altered(self, active, finished):
        # every request's third token leaves the engine as another one
        # (and is what the next step consumes): each request still ends
        # FINISHED on its budget
        for i in active:
            r = self._slot_req[i]
            if r is not None and len(r.output) == 2:
                self._tok[i] = int(self._tok[i]) ^ 1      # its neighbour
        return commit(self, active, finished)

    monkeypatch.setattr(Engine, "_commit", altered)
    broken = _rehearse(cell, capsys, monkeypatch, 2**31 + 41)
    assert broken["correct"] is False and broken["failed"] == 0
    c = broken["compared"]["served_gap_max"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    """At a size a test can hold (the cell's own family at width 512 and
    16 layers, bf16 weights): greedy tokens of the float32 reference
    read a gap of nought, and the tokens the reference puts first with
    its matrices rounded to fp8 are NOT correct by the cell's own
    limits. (On the chip at the cell's size: `python3
    benchmark/runners/serve.py --workload <cell> --seeds a,b,c --control
    float8_e4m3fn`; PERF.md has the readings the limits were set from.)"""
    import jax.numpy as jnp

    from benchmark import run as harness
    from benchmark import weights
    from benchmark.runners import serve
    _, _, _, spec, sizes = harness.load_cell(cell)
    check = dict(spec["served_check"], pad_to=64)
    sizes.update(hidden_size=512, intermediate_size=1536,
                 num_hidden_layers=16, num_attention_heads=8,
                 num_key_value_heads=4, vocab_size=8192)
    model, _ = weights.build(sizes["program"], sizes, 5)
    values = weights.named_values(model)
    ref = serve.importlib.import_module(
        f"benchmark.reference.{sizes['reference']}")
    rng = np.random.default_rng(5)
    sample = []
    for n in (40, 24, 33, 17, 29, 36):
        ids, toks = rng.integers(1, sizes["vocab_size"], n).tolist(), []
        for _ in range(16):      # greedy, by the float32 reference
            full = ids + toks
            toks.append(int(ref.forward_logits(
                values, sizes, full + [1] * (64 - len(full))
            )[len(full) - 1].argmax()))
        sample.append({"prompt": ids, "tokens": toks})
    sound = serve.served_check(values, sizes, sample, check)
    assert sound["ok"] and sound["served_gap_max"] == 0.0
    control = serve.served_check(values, sizes, sample, check,
                                 control=jnp.float8_e4m3fn)
    assert not control["ok"]
    assert control["served_gap_mean"] > check["gap_mean_limit"]
    assert control["program"] == {k: sound[k] for k in control["program"]}
