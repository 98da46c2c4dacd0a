"""The 10 assigned architecture configs carry the exact assigned numbers."""
import json
import math
import os

import jax
import pytest

from repro.configs.base import SHAPES, all_archs, cells, get_config
from repro.models import model as M

ASSIGNED = {
    # name: (layers, d_model, heads, kv, d_ff, vocab)
    "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
    "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
    "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
    "stablelm-3b": (32, 2560, 32, 32, 6912, 50304),
    "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
    "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
    "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
    "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
    "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
    "whisper-base": (6, 512, 8, 8, 2048, 51865),
}


def test_all_archs_registered():
    assert set(all_archs()) == set(ASSIGNED)


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_assigned_numbers(name):
    cfg = get_config(name)
    L, d, h, kv, ff, v = ASSIGNED[name]
    assert cfg.n_layers == L and cfg.d_model == d
    assert cfg.n_heads == h and cfg.n_kv_heads == kv
    assert cfg.d_ff == ff and cfg.vocab_size == v


def test_moe_structure():
    ds = get_config("deepseek-moe-16b")
    assert ds.n_experts == 64 and ds.top_k == 6 and ds.n_shared_experts == 2
    phi = get_config("phi3.5-moe-42b-a6.6b")
    assert phi.n_experts == 16 and phi.top_k == 2


def test_param_counts_sane():
    # analytic counts should land near the advertised sizes
    approx = {
        "qwen2-vl-7b": 7e9, "deepseek-moe-16b": 16e9,
        "phi3.5-moe-42b-a6.6b": 42e9, "gemma3-12b": 12e9,
        "starcoder2-3b": 3e9, "qwen2-0.5b": 0.5e9,
    }
    for name, n in approx.items():
        got = get_config(name).n_params()
        assert 0.5 * n < got < 1.9 * n, (name, got, n)


def test_cells_40_with_documented_skips():
    rows = list(cells())
    assert len(rows) == 40
    skips = [(c.name, s.name) for c, s, skip in rows if skip]
    # long_500k runs only for sub-quadratic archs (xlstm, hymba)
    assert all(s == "long_500k" for _, s in skips)
    ran_long = [c.name for c, s, skip in rows
                if s.name == "long_500k" and not skip]
    assert sorted(ran_long) == ["hymba-1.5b", "xlstm-350m"]
    assert len(skips) == 8


def test_shapes_assigned():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288


# published parameter counts of the full models (config.json, tied heads)
PUBLISHED_PARAMS = {"starcoder2-3b": 3_030_371_328,
                    "qwen2-0.5b": 494_032_768}


def _tree_params(cfg) -> int:
    shapes = jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


@pytest.mark.parametrize("name", sorted(PUBLISHED_PARAMS))
def test_param_tree_is_published_count(name):
    """The registry's parameter tree holds the published model, tied head
    included, and the analytic count agrees with it to the parameter."""
    cfg = get_config(name)
    assert _tree_params(cfg) == PUBLISHED_PARAMS[name]
    assert cfg.n_params() == PUBLISHED_PARAMS[name]


def test_starcoder2_matches_benchmark_config():
    """The benchmark's starcoder2-3b file and the registry agree on the
    published tied head and RoPE base."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "chip", "configs", "starcoder2-3b.json")
    with open(path) as f:
        c = json.load(f)
    cfg = get_config("starcoder2-3b")
    assert cfg.tie_embeddings is c["tie_word_embeddings"] is True
    assert cfg.rope_theta == c["rope_theta"] == 999999.4420358813
