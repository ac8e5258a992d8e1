"""The q, k and v projections read their weights where they lie in the layer
stack: checked in the programs the chip's own compiler builds, with no chip.

``tests/tpu_preflight_worker.py`` compiles the decode step and a fresh
prefill piece for a TPU v5e by the README's pre-flight recipe (libtpu
compiles for a described chip), at the benchmark's seven configurations
(published widths, two layers (of the window stack: its global layer and
a window layer, both K/V leaves, all 64 experts under each), one period of the hybrid, one block of
each kind of the pattern stack with 8 experts held, two blocks of the
two-mixer stack, or three layers of the short-convolution stack: both
operators, the dense MLP and two layers of all 32 experts). Asked of each
executable: ``weight_copy_bytes == 0`` (no per-layer slice of an integer
weight stack is copied, i.e. transposed, before its matmul:
profiling/compile_stats.py) and a Mosaic call is still compiled in.

A transposition of a projection's OUTPUT (the head split) can reach back
through the dot to its weight: with ``qkv_proj`` as it stood before PR 31
every case here fails (mistral 25,165,824 bytes, qwen2 16,515,072, the
hybrid 14,745,600). A model of d 512 with 4 / 2 heads of 128 shows the same
three copies in a quarter of the compile time, should this file ever cost
the suite too much.

The hybrid's decode step also holds ``state_copy_bytes == 0``: its delta
rule's state is updated inside the cache's layer stack by
``kvmini_linear_decode`` (ops/linear_decode.py), one call a linear layer of
the period beside the one ``kvmini_dense_decode``; the two pattern stacks'
the same for their Mamba-2 state and ``kvmini_ssm_decode`` (ops/ssm_decode.py).

The two stacks of routed GLU experts compile a prefill piece of 512 tokens,
which takes the grouped dispatch (models/moe.py): ``weight_copy_bytes == 0``
there says that the ``gmm`` kernel reads the int8 expert stacks where they
lie, and the worker's ``grouped`` entry that the walk's expert layers lower
ONE body and that the benchmark's readers find every call by its cut name.
The decode step of all three stacks of routed experts sweeps the touched
experts (``emm``, one call an expert layer): the worker's ``swept`` entry
says the same of it.

One file, one subprocess: libtpu is loaded in the child alone (``--dist
loadfile`` keeps this file in one worker), and the child is held to a
timeout, so nothing here can hang the suite. Skips, with the reason, where
libtpu or the topology cannot be had.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from tests import env_guards

MODELS = ("mistral-7b-int8", "qwen2-7b-int8", "olmo-hybrid-7b-int8",
          "nemotron-3-super-ep4-int8", "falcon-h1-34b-int8", "lfm2-8b-a1b-int8",
          "smallthinker-21b-pp4-int8")
CASES = [f"{m}:{s}" for m in MODELS for s in ("decode", "prefill")]
# fourteen compiles take ~230 s here; under a loaded suite several times that
TIMEOUT_S = 900


@pytest.fixture(scope="module")
def compiled():
    env_guards.require_child_jax()
    worker = Path(__file__).parent / "tpu_preflight_worker.py"
    try:
        p = subprocess.run(
            [sys.executable, str(worker), *CASES],
            capture_output=True, text=True, timeout=TIMEOUT_S,
            cwd=Path(__file__).parent.parent,
        )
    except subprocess.TimeoutExpired:
        pytest.skip(f"the chipless compiles did not finish in {TIMEOUT_S} s here")
    assert p.returncode == 0, f"rc={p.returncode}\n{p.stdout[-2000:]}\n{p.stderr[-4000:]}"
    out = json.loads(p.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(out["skip"])
    return out["cases"]


@pytest.mark.parametrize("case", CASES)
def test_projections_read_their_weights_in_place(compiled, case):
    stats = compiled[case]
    assert stats["weight_copy_bytes"] == 0, (
        f"{case}: {stats['weight_copy_bytes']} bytes of per-layer integer "
        "weight slices are copied before their matmul")
    # lfm2's decode step holds no attention kernel (64-wide heads, which
    # ops/paged_attention.py dense_decode_block turns away: ROADMAP A), but
    # its expert layers' sweeps
    assert stats["mosaic_calls"] >= 1, f"{case}: no Pallas kernel compiled in"


def test_the_hybrids_decode_step_updates_its_state_in_place(compiled):
    """No layer of the float32 state stack is sliced out, written back or
    copied around the kernel's calls, and the calls are there (the parent's
    step held ``kvmini_dense_decode`` alone: ``mosaic_calls`` 1, and read
    318,504,960 bytes here: per linear layer two fused slices and a
    write-back of 35,389,440). With the call handed the layer's slice
    (``dynamic_index_in_dim``, the call, ``dynamic_update_index_in_dim``)
    this case read ``mosaic_calls`` 4 and ``state_copy_bytes`` 212,336,640:
    three slices out and three write-backs, a copy each way around an opaque
    call, four passes over the state where the jnp path makes three."""
    stats = compiled["olmo-hybrid-7b-int8:decode"]
    assert stats["state_copy_bytes"] == 0, (
        f"{stats['state_copy_bytes']} bytes of the recurrent state's layers are "
        "sliced out of their stack or written back outside the kernel")
    assert stats["mosaic_calls"] >= 4, (
        f"mosaic_calls {stats['mosaic_calls']}: the period's three linear "
        "layers do not each hold kvmini_linear_decode")


# state-space blocks and attention calls of each pattern stack as the worker
# cuts it: "M*E" (one Mamba-2 block, one attention block) and "PP" (two blocks
# of both mixers)
PATTERN_STACKS = {"nemotron-3-super-ep4-int8": (1, 1), "falcon-h1-34b-int8": (2, 2)}


@pytest.mark.parametrize("model", sorted(PATTERN_STACKS))
def test_the_pattern_stacks_decode_step_updates_its_state_in_place(compiled, model):
    """No layer of the float32 Mamba-2 state stack is sliced out, written
    back or copied around ``kvmini_ssm_decode`` (ops/ssm_decode.py), and the
    calls are there: one a state-space block beside one
    ``kvmini_dense_decode`` an attention block. Until PR 37 the update was
    jnp (models/mamba.py ``recurrent_step``) and these cases only recorded
    its traffic: the two-mixer stack's decode step read ``mosaic_calls`` 2
    and ``state_copy_bytes`` 402,653,184 (two blocks at the published widths,
    state 32 x 128 x 256: 16 slots x 4.19 MB x 3 a layer, the slice out, the
    update and the write-back); the single-mixer stack's read ``mosaic_calls``
    1 and ``state_copy_bytes`` 0 (its one state-space block is the whole
    stack, so nothing was sliced: there the calls are the bar)."""
    ssm_blocks, attention_calls = PATTERN_STACKS[model]
    stats = compiled[f"{model}:decode"]
    assert stats["state_copy_bytes"] == 0, (
        f"{stats['state_copy_bytes']} bytes of the state-space state's layers are "
        "sliced out of their stack or written back outside the kernel")
    assert stats["mosaic_calls"] >= ssm_blocks + attention_calls, (
        f"mosaic_calls {stats['mosaic_calls']}: the {ssm_blocks} state-space "
        "blocks do not each hold kvmini_ssm_decode")


def test_the_two_mixer_stack_holds_a_kernel_a_block_and_its_prefill_traffic_is_recorded(compiled):
    """Two blocks at the published widths (hidden 5120, attention 20 / 4 heads
    x 128, state 32 x 128 x 256): one ``kvmini_dense_decode`` a block in the
    decode step (G = 5), one flash prefill a block in ``prefill[128]``. The
    prefill piece's ``state_copy_bytes`` is a number to record, not a bar: a
    fresh prefill starts from a zero state and ``chunked_scan`` writes each
    layer's new state into the stack once (67,108,864 for two blocks)."""
    decode, prefill = (compiled[f"falcon-h1-34b-int8:{s}"] for s in ("decode", "prefill"))
    assert decode["mosaic_calls"] >= 2 and prefill["mosaic_calls"] >= 2
    assert isinstance(prefill["state_copy_bytes"], int) and prefill["state_copy_bytes"] >= 0
    print("falcon-h1-34b-int8 prefill state_copy_bytes:", prefill["state_copy_bytes"])


# expert layers of each stack of routed GLU experts as the worker cuts it
GROUPED = {"lfm2-8b-a1b-int8": 2, "smallthinker-21b-pp4-int8": 2}


@pytest.mark.parametrize("model", sorted(GROUPED))
def test_a_long_piece_is_grouped_one_body_a_walk_and_the_readers_find_its_calls(compiled, model):
    """``prefill[512]`` at the published widths: two ``gmm`` calls an expert
    layer (the gated pair with its activation, the down projection) over the
    int8 stacks in place (``weight_copy_bytes`` 0 is the bar above), ONE
    ``_run_grouped`` function in the lowered module however many layers call
    it (each call site lowered on its own is what made PR 41's set-up 14 s
    longer), and every call an expert stack's operation to
    ``reads_expert_stack`` of the stack's roofline module under the name a
    trace shows: result, then operands, cut to 96 characters. A call they
    missed would leave ``*_moe_prefill_flop_share`` dividing by too little
    time."""
    stats = compiled[f"{model}:prefill"]
    layers = GROUPED[model]
    found = {k: stats["grouped"][k] for k in ("bodies", "calls", "found")}
    assert found == {"bodies": 1, "calls": 2 * layers, "found": 2 * layers}, stats["grouped"]
    assert stats["mosaic_calls"] >= 2 * layers + 1        # and a flash prefill call at least


# expert layers of each stack of routed experts as the worker cuts it
SWEPT = {**GROUPED, "nemotron-3-super-ep4-int8": 1}


@pytest.mark.parametrize("model", sorted(SWEPT))
def test_a_decode_step_sweeps_one_body_a_walk_and_the_readers_find_its_calls(compiled, model):
    """The decode step at the published widths: ONE ``emm`` call an expert
    layer over the int8 stacks in place (``weight_copy_bytes`` 0 is the bar
    above: the latent experts' walk hands the kernel its stack and index like
    the other two), ONE ``expert_sweep`` function in the lowered module, and
    every call an expert stack's operation to ``reads_expert_stack`` of the
    stack's roofline module under the name a trace shows. A call they missed
    would leave ``*_moe_decode_ms`` the leftovers and ``*_expert_hbm_share``
    hundreds of percent."""
    stats = compiled[f"{model}:decode"]
    layers = SWEPT[model]
    found = {k: stats["swept"][k] for k in ("bodies", "calls", "found")}
    assert found == {"bodies": 1, "calls": layers, "found": layers}, stats["swept"]
    assert stats["mosaic_calls"] >= layers
