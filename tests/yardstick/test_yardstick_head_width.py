"""An attention narrower than the hidden size, as data (PR 34): the harness
reads ``head_dim`` from the configuration's file and hands it to the program
where it is not ``hidden_size / num_attention_heads``; the four accepted
configurations build the ``ModelConfig`` they built before; and a dense
decoder of hidden 128 with 2 query / 1 KV heads of 32 (q width 64) goes
through ``model_config``, the program's engine and ``bench/check.py`` against
the plain reference, with the lower-precision control and altered tokens.
At the published narrow widths (hidden 5120, 20 / 4 heads of 128) the decode
step and a prefill piece are compiled for a v5e with no chip
(``narrow_preflight_worker.py``)."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import check, config
from tests import env_guards
from tests.yardstick.test_yardstick_correct import (ASKS, CONTROL_ASKS, PADS, by_hand, reference,
                                                    serve, tiny)

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
system = config.load_module("systems", "engine")


def narrow(family):
    """The family's tiny configuration (a dict, no entry in BENCHMARK.json) with
    2 query heads on 1 KV head, 32 wide: q and o are 128 x 64, k and v 128 x 32."""
    cfg = tiny(family)
    cfg["model"].update(num_attention_heads=2, num_key_value_heads=1, head_dim=32,
                        num_hidden_layers=2)
    assert cfg["model"]["hidden_size"] == 128
    return cfg


FOUR = ("mistral-7b-int8", "qwen2-7b-int8", "olmo-hybrid-7b-int8", "nemotron-3-super-ep4-int8")


@pytest.mark.parametrize("cell", [next(w["name"] for w in BM["workloads"]
                                       if w["config"] == c["name"]) for c in BM["configs"]])
@pytest.mark.parametrize("rehearse", [False, True], ids=["full", "tiny"])
def test_explicit_head_dim_is_handed_over_only_where_the_width_differs(cell, rehearse):
    """``explicit_head_dim`` is the one field ``model_config`` sets that the parent
    of PR 34 did not. It is set if and only if the file's ``head_dim`` is not
    ``hidden_size // num_attention_heads``, so a later configuration of either
    kind passes here; the four accepted ones leave it unset, and their
    ``ModelConfig`` compares equal to the parent's."""
    loaded = config.load_cell(cell, rehearse=rehearse)["config"]
    m = loaded["model"]
    built = system.model_config(m)
    assert built.head_dim == m["head_dim"] and built.d_model == m["hidden_size"]
    even = m["head_dim"] == m["hidden_size"] // m["num_attention_heads"]
    assert (built.explicit_head_dim is None) is even
    assert built.explicit_head_dim in (None, m["head_dim"])
    if loaded["name"] in FOUR:
        assert even and built.d_model == built.n_heads * built.head_dim


def test_head_dim_is_the_files_and_a_file_without_it_is_refused_in_words():
    m = {**config.load_cell("mistral-7b.chat")["config"]["model"], "hidden_size": 5120,
         "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 128}
    built = system.model_config(m)
    assert (built.head_dim, built.explicit_head_dim, built.d_model) == (128, 128, 5120)
    assert built.n_heads * built.head_dim == 2560     # q and o: 5120 x 2560
    wide = system.model_config({**m, "hidden_size": 2048, "head_dim": 256})
    assert wide.head_dim == 256                        # wider than the residual stream too
    del m["head_dim"]
    with pytest.raises(KeyError, match="gives no head_dim"):
        system.model_config(m)


@pytest.mark.parametrize("family", ["mistral", "qwen"])
def test_reference_against_float64_by_hand_at_a_narrow_attention(family):
    m = narrow(family)["model"]
    seq = [int(t) for t in np.random.default_rng(4).integers(3, m["vocab_size"], 40)]
    want = by_hand(12, m, seq)
    got = reference.logits_after(12, m, [seq], [1])[0]
    assert got.shape == (39, m["vocab_size"])
    assert np.max(np.abs(got - want[:-1])) < 2e-4 * np.max(np.abs(want))


@pytest.mark.parametrize("family", ["mistral", "qwen"])
def test_served_path_agrees_at_a_narrow_attention_and_altered_tokens_do_not(family):
    cfg = narrow(family)
    built = system.model_config(cfg["model"])
    assert built.head_dim == 32 and built.n_heads * built.head_dim == 64 != built.d_model
    seqs, first = serve(cfg, 5, ASKS)
    sound, positions = check.served_numbers(5, cfg["model"], seqs, first, PADS)
    assert positions == sum(n for _, n in ASKS)
    assert sound["gap_mean"] < 3e-3 and sound["gap_max"] < 0.15
    wrong = [s[:f] + [(t + 1) % cfg["model"]["vocab_size"] for t in s[f:]]
             for s, f in zip(seqs, first)]
    broken, _ = check.served_numbers(5, cfg["model"], wrong, first, PADS)
    ok, lines = check.within(broken, check.limits_for(cfg["name"]))
    assert not ok and any("OVER" in line for line in lines)


# The control at the narrow width: 2 layers, weights at 1/sqrt(hidden), 650 served positions.
# Readings of gap_mean (CPU, seeds 5-8 and 11-18, my runs, PR 34): mistral sound
# 0.00031-0.00066, w8a8 0.00107-0.00237; qwen sound 0.00008-0.00052, w8a8 0.00080-0.00212: no
# seed of the twelve overlaps, but the two lie closer than on the chip (there 0.017 against
# 0.108 and more), so the limits sit midway and the test keeps to one seed (15: 0.00037
# against 0.00184, 0.00042 against 0.00187). At 8 layers, test_yardstick_correct's size, this
# width separates worse (qwen sound up to 0.0017, w8a8 from 0.0016), so 2 it is. gap_max is
# held against a wrong token.
NARROW_LIMITS = {"mistral": {"gap_mean": 0.00085, "gap_max": 0.6},
                 "qwen": {"gap_mean": 0.00065, "gap_max": 0.6}}


@pytest.mark.parametrize("family", ["mistral", "qwen"])
def test_the_programs_lower_precision_reads_not_correct_at_a_narrow_attention(family):
    numbers = {}
    for mode in ("dequant", "w8a8"):
        cfg = narrow(family)
        cfg["engine"]["quant_mode"] = mode
        seqs, first = serve(cfg, 15, CONTROL_ASKS)
        numbers[mode], _ = check.served_numbers(
            15, cfg["model"], seqs, first, {"pad_sequence_to": 256, "pad_rows_to": 64})
    assert check.within(numbers["dequant"], NARROW_LIMITS[family])[0], numbers
    ok, lines = check.within(numbers["w8a8"], NARROW_LIMITS[family])
    assert not ok and any("gap_mean" in line and "OVER" in line for line in lines), numbers


@pytest.fixture(scope="module")
def compiled_for_the_chip():
    """``narrow_preflight_worker.py`` in a process of its own (libtpu is loaded
    there alone), held to a timeout: ~30 s here, several times that under a
    loaded suite."""
    env_guards.require_child_jax()
    try:
        p = subprocess.run([sys.executable, str(Path(__file__).parent / "narrow_preflight_worker.py")],
                           capture_output=True, text=True, timeout=600, cwd=ROOT)
    except subprocess.TimeoutExpired:
        pytest.skip("the chipless compiles did not finish in 600 s here")
    assert p.returncode == 0, f"rc={p.returncode}\n{p.stdout[-2000:]}\n{p.stderr[-4000:]}"
    out = json.loads(p.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(out["skip"])
    return out


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_the_chips_compiler_takes_the_narrow_published_widths(compiled_for_the_chip, step):
    """Hidden 5120 with 20 query / 4 KV heads of 128, two layers, compiled for a
    v5e with no chip: the decode step and a fresh prefill piece of 128 lower,
    each with its Pallas kernel, and no weight slice is copied before its matmul."""
    out = compiled_for_the_chip
    assert (out["head_dim"], out["explicit_head_dim"], out["q_width"]) == (128, 128, 2560)
    stats = out["cases"][step]
    assert stats["mosaic_calls"] >= 1, f"{step}: no Pallas kernel compiled in"
    assert stats["weight_copy_bytes"] == 0
