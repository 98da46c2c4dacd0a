"""Sizes, helpers and the `small` fixture shared by the benchmark's tests
(test modules import the fixture by name)."""
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

# every LM cell at reduced widths: its published structure (norm, MLP,
# biases, tying, GQA), small sizes
SMALL_LM = {"num_hidden_layers": 2, "hidden_size": 64,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "intermediate_size": 128, "vocab_size": 512}
PROGRAM = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size"}
SMALL_SEQ = 64
SMALL_PS = {"jobs": 4}



def result_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def workloads(chips=None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    return [w["name"] for w in cells if chips is None or w["chips"] == chips]


@pytest.fixture
def small(monkeypatch):
    """The harness, with every configuration and traffic mix cut to a
    size the CPU runs in seconds."""
    import harness
    config, traffic = harness.config, harness.traffic

    def small_config(name):
        c = config(name)
        if "hidden_size" in c:
            c.update(SMALL_LM)
            c["program"]["replace"].update(
                {PROGRAM[k]: v for k, v in SMALL_LM.items()})
            c["program"]["knobs"] = {"ce_chunk": 96}
        return c

    def small_traffic(name):
        t = traffic(name)
        if t["driver"] == "trainer":
            t["seq_len"] = SMALL_SEQ
        else:
            t.update(SMALL_PS)
        return t

    monkeypatch.setattr(harness, "config", small_config)
    monkeypatch.setattr(harness, "traffic", small_traffic)
    return harness
