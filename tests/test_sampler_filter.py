"""The sampler sorts only when a row asks for a filter (runtime/sampling.py
``filter_logits``): against the two-sort function it replaced, kept here as
the oracle; the neighbour rule; where the sort stands in the compiled
programs; and the engine's count of the steps that sorted.

Beside tests/test_runtime.py's sampler tests in subject, in a file of its own
because that module is marked slow as a whole and these belong to tier 1."""

import asyncio
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import init_params
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest
from kserve_vllm_mini_tpu.runtime.sampling import filter_logits, sample_tokens

V = 5000


def two_sort_filter_logits(logits, temperature, top_k, top_p):
    """``filter_logits`` as it stood before the sort became conditional: two
    stable sorts of every batch, and a top-p cut for every row."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1)
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_k - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    scaled = jnp.where((top_k[:, None] > 0) & (scaled < kth), -jnp.inf, scaled)
    sorted_desc2 = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs_sorted = jax.nn.softmax(sorted_desc2, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    inside = cum - probs_sorted < top_p[:, None]
    cut = jnp.min(jnp.where(inside, sorted_desc2, jnp.inf), axis=-1)
    scaled = jnp.where(scaled < cut[:, None], -jnp.inf, scaled)
    greedy_lg = jnp.where(jax.nn.one_hot(greedy, V, dtype=bool), 0.0, -jnp.inf)
    return jnp.where(temperature[:, None] > 0, scaled, greedy_lg)


NEW = jax.jit(filter_logits)
OLD = jax.jit(two_sort_filter_logits)


def mixed_batch(seed: int, rows: int = 8):
    """Logits rounded to a tenth (so every row holds ties, the k-th entry
    among them) under mixed parameters; row 0 always filters, so the batch
    takes the sort branch."""
    rng = np.random.default_rng(seed)
    logits = np.round(rng.normal(size=(rows, V)) * 3, 1).astype(np.float32)
    temps = rng.choice([0.0, 0.3, 0.7, 1.0, 1.5], size=rows).astype(np.float32)
    top_k = rng.choice([0, 1, 5, 40, V], size=rows).astype(np.int32)
    top_p = rng.choice([1.0, 0.9, 0.5, 0.1], size=rows).astype(np.float32)
    temps[0], top_k[0], top_p[0] = 0.7, (0, 40)[seed % 2], 0.9
    return logits, temps, top_k, top_p


@pytest.mark.parametrize("seed", range(12))
def test_one_sort_is_bit_identical_to_two(seed):
    """Every row with a top-p cut or temperature 0 reads bit for bit what the
    two-sort function gave; a row with ``top_p`` 1.0 differs from it only by
    keeping the tail the old cumulative sum rounded away."""
    logits, temps, top_k, top_p = mixed_batch(seed)
    new = np.asarray(NEW(logits, temps, top_k, top_p))
    old = np.asarray(OLD(logits, temps, top_k, top_p))
    for r in range(len(temps)):
        if top_p[r] < 1.0 or temps[r] == 0:
            np.testing.assert_array_equal(new[r], old[r], err_msg=f"row {r}")
        else:
            kept = np.isfinite(new[r])
            # top-k alone: the k largest (and their ties) at their scaled values
            np.testing.assert_array_equal(new[r][kept], (logits[r] / temps[r])[kept])
            assert np.isfinite(old[r]).sum() <= kept.sum()
            if top_k[r] == 0:
                assert kept.all()
            else:
                kth = np.sort(logits[r] / temps[r])[::-1][min(top_k[r], V) - 1]
                np.testing.assert_array_equal(kept, logits[r] / temps[r] >= kth)


@pytest.mark.parametrize("temperature", [0.3, 1.0, 1.7])
@pytest.mark.parametrize("neighbours", ["alone", "filtered", "greedy", "top_k_only"])
def test_unfiltered_row_is_untouched_whatever_its_neighbours_ask(neighbours, temperature):
    """``top_k`` 0, ``top_p`` 1.0, temperature > 0: exactly ``logits /
    temperature`` through either branch, and no entry masked. At the parent
    such a row lost the tail its float32 cumulative sum rounded to 1.0."""
    rng = np.random.default_rng(7)
    row = (rng.normal(size=(1, V)) * 4).astype(np.float32)
    other = {"alone": None, "filtered": (0.7, 0, 0.9), "greedy": (0.0, 0, 1.0),
             "top_k_only": (1.0, 5, 1.0)}[neighbours]
    logits = row if other is None else np.concatenate([row, row[:, ::-1]])
    temps = np.asarray([temperature] + ([] if other is None else [other[0]]), np.float32)
    top_k = np.asarray([0] + ([] if other is None else [other[1]]), np.int32)
    top_p = np.asarray([1.0] + ([] if other is None else [other[2]]), np.float32)
    out = np.asarray(NEW(logits, temps, top_k, top_p))
    assert np.isfinite(out[0]).all()
    np.testing.assert_array_equal(out[0], row[0] / np.float32(temperature))


def test_the_two_sort_function_cut_the_tail_of_a_row_that_asked_for_none():
    """Why the rule above is new: with ``top_p`` 1.0 the float32 cumulative
    sum reaches 1.0 before the last entries, and the old function masked
    them (~1e-6 of the mass)."""
    rng = np.random.default_rng(11)
    logits = (rng.normal(size=(6, V)) * 3).astype(np.float32)
    off = (np.full(6, 0.3, np.float32), np.zeros(6, np.int32), np.ones(6, np.float32))
    scaled = logits / np.float32(0.3)
    old = np.asarray(OLD(logits, *off))
    lost = old == -np.inf
    assert lost.any(axis=-1).sum() >= 3 and not lost.all(axis=-1).any()
    assert (np.where(lost, jax.nn.softmax(scaled, axis=-1), 0.0).sum(axis=-1) < 1e-4).all()
    np.testing.assert_array_equal(np.asarray(NEW(logits, *off)), scaled)


def test_greedy_rows_are_the_argmax_one_hot_in_both_branches():
    logits, temps, top_k, top_p = mixed_batch(3)
    temps[:] = 0.0
    skipped = np.asarray(NEW(logits, temps, top_k, top_p))
    temps[0] = 0.7  # row 0 filters: every other row goes through the sort branch
    sorted_ = np.asarray(NEW(logits, temps, top_k, top_p))
    np.testing.assert_array_equal(skipped[1:], sorted_[1:])
    assert ((skipped == 0).sum(axis=-1) == 1).all()
    np.testing.assert_array_equal(skipped.argmax(-1), logits.argmax(-1))


# -- where the sort stands in the compiled programs ---------------------------


def hlo_sorts(hlo: str) -> tuple[int, int]:
    """(``sort`` instructions in the module, those of them that run only under
    a ``conditional``) of a compiled module's text: computations are flat
    there, so the guarded ones are those a conditional's branches reach."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s*(?:\(.*)?\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(1).lstrip("%")
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    calls = {n: {c.lstrip("%") for ln in body for c in re.findall(r"%[\w.\-]+", ln)
                 if c.lstrip("%") in comps and c.lstrip("%") != n}
             for n, body in comps.items()}
    guarded, todo = set(), []
    for body in comps.values():
        for ln in body:
            if " conditional(" in ln:
                for group in re.findall(
                        r"(?:branch_computations=\{([^}]*)\}|(?:true|false)_computation=(%?[\w.\-]+))",
                        ln):
                    todo += [c.strip().lstrip("%") for g in group if g for c in g.split(",")]
    while todo:
        c = todo.pop()
        if c in comps and c not in guarded:
            guarded.add(c)
            todo += calls[c]

    def count(names):
        return sum(bool(re.search(r"\bsort\(", ln)) for n in names for ln in comps[n])

    return count(comps), count(guarded)


def stablehlo_sorts(lowered) -> tuple[int, int]:
    """(``stablehlo.sort`` operations a lowered module runs, those of them
    outside every ``stablehlo.case`` / ``stablehlo.if``), following calls from
    ``main``: ``jnp.sort`` lowers to a private function of its own."""
    module = lowered.compiler_ir()
    funcs = {}
    for op in module.body.operations:
        if op.operation.name == "func.func":
            funcs[str(op.attributes["sym_name"]).strip('"')] = op

    def walk(op, skip_conditionals):
        n = 0
        for region in op.operation.regions:
            for block in region.blocks:
                for child in block.operations:
                    name = child.operation.name
                    if name == "stablehlo.sort":
                        n += 1
                    elif skip_conditionals and name in ("stablehlo.case", "stablehlo.if"):
                        continue
                    elif name == "func.call":
                        # once a call site: two calls of one @sort are two sorts
                        callee = str(child.attributes["callee"]).lstrip("@").strip('"')
                        n += walk(funcs[callee], skip_conditionals)
                    n += walk(child, skip_conditionals)
        return n

    return walk(funcs["main"], False), walk(funcs["main"], True)


def sampler_args(rows: int, vocab: int):
    return (jnp.zeros((rows, vocab), jnp.float32), jnp.zeros((rows,), jnp.float32),
            jnp.zeros((rows,), jnp.int32), jnp.ones((rows,), jnp.float32))


def test_filter_logits_compiles_to_one_sort_under_a_conditional():
    lowered = jax.jit(filter_logits).lower(*sampler_args(4, V))
    assert hlo_sorts(lowered.compile().as_text()) == (1, 1)
    assert stablehlo_sorts(lowered) == (1, 0)
    # and the oracle reads as what it is: two sorts, neither guarded
    old = jax.jit(two_sort_filter_logits).lower(*sampler_args(4, V))
    assert hlo_sorts(old.compile().as_text()) == (2, 0)
    assert stablehlo_sorts(old) == (2, 2)


def test_sample_tokens_sorts_nowhere_else():
    logits, temps, top_k, top_p = sampler_args(4, V)
    lowered = jax.jit(sample_tokens).lower(logits, jax.random.PRNGKey(0), temps, top_k, top_p)
    assert stablehlo_sorts(lowered) == (1, 0)


CFG = get_config("llama-tiny")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def tiny_engine(params, **kw) -> Engine:
    return Engine(params, CFG, EngineConfig(max_slots=4, max_seq_len=128, max_prefill_len=64,
                                            min_prefill_bucket=16, **kw))


def _jitted(fn):
    """The jitted function under the compile-statistics wrapper."""
    return getattr(fn, "_fn", fn)


@pytest.mark.parametrize("program", ["decode[chunk=1]", "decode[chunk=4]", "decode.masked", "first"])
def test_engine_programs_hold_no_sort_outside_a_conditional(params, program):
    """Every program of the engine that samples goes through the one
    function: the lowered step holds its one sort, inside the conditional."""
    eng = tiny_engine(params)
    S, vocab = 4, CFG.vocab_size
    temps, top_k, top_p, pres, freqs = eng._get_sampling_arrays()
    rng = jax.random.PRNGKey(0)
    toks, lens = jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32)
    packed = jnp.zeros((S, (vocab + 7) // 8), jnp.uint8)
    if program == "first":
        lowered = _jitted(eng._step("first")).lower(
            jnp.zeros((vocab,), jnp.float32), rng, temps[0], top_k[0], top_p[0],
            packed[0], jnp.asarray(False))
    elif program == "decode.masked":
        lowered = _jitted(eng._step("decode_masked")).lower(
            eng.params, eng._cache, toks, lens, temps, top_k, top_p, rng,
            eng._counts, pres, freqs, packed, jnp.zeros((S,), bool))
    else:
        chunk = int(program[len("decode[chunk="):-1])
        lowered = _jitted(eng._step("decode", chunk)).lower(
            eng.params, eng._cache, toks, lens, temps, top_k, top_p, rng,
            eng._counts, pres, freqs)
    assert stablehlo_sorts(lowered) == (1, 0)


def test_spec_step_holds_its_two_sorts_inside_conditionals(params):
    """The drafter's q and the target's p each come from the one function."""
    from kserve_vllm_mini_tpu.runtime.steps import build_spec_step_sampled

    spec = build_spec_step_sampled(CFG, CFG, k=3)
    eng = tiny_engine(params)
    temps, top_k, top_p, _, _ = eng._get_sampling_arrays()
    z = jnp.zeros((4,), jnp.int32)
    lowered = spec.lower(eng.params, eng._cache, eng.params, eng._cache, z, z,
                         temps, top_k, top_p, jax.random.PRNGKey(0))
    assert stablehlo_sorts(lowered) == (2, 0)


# -- the counter --------------------------------------------------------------


def drain(handle) -> list[int]:
    out = []
    while True:
        kind, *rest = handle.events.get(timeout=300)
        if kind != "token":
            return out
        out.append(rest[0])


def metrics_of(eng) -> dict[str, float]:
    from aiohttp.test_utils import TestClient, TestServer

    from kserve_vllm_mini_tpu.runtime.server import make_app
    from kserve_vllm_mini_tpu.runtime.tokenizer import load_tokenizer

    async def scrape():
        async with TestClient(TestServer(make_app(eng, load_tokenizer(None), "llama-tiny"))) as c:
            return await (await c.get("/metrics")).text()

    return {ln.split()[0]: float(ln.split()[1]) for ln in asyncio.run(scrape()).splitlines()
            if ln and not ln.startswith("#") and len(ln.split()) == 2}


NAME = "kvmini_tpu_sampler_filter_steps_total"


@pytest.mark.parametrize("request_kw", [
    {}, {"temperature": 0.8}, {"temperature": 0.0, "top_p": 0.5, "top_k": 3},
], ids=["greedy", "plain_temperature", "greedy_with_unused_filter"])
def test_counter_stays_zero_while_no_sampled_row_filters(params, request_kw):
    eng = tiny_engine(params)
    eng.start()
    try:
        handles = [eng.submit(GenRequest(prompt_tokens=[3, 4, 5 + i], max_new_tokens=10,
                                         **request_kw)) for i in range(3)]
        assert [len(drain(h)) for h in handles] == [10, 10, 10]
        values = metrics_of(eng)
    finally:
        eng.stop()
    assert eng.stats["decode_steps"] >= 9
    assert values[NAME] == eng.stats["sampler_filter_steps"] == 0
    assert values["kvmini_tpu_decode_steps_total"] == eng.stats["decode_steps"]


@pytest.mark.parametrize("decode_chunk", [1, 4])
@pytest.mark.parametrize("request_kw", [{"top_p": 0.9}, {"top_k": 5}], ids=["top_p", "top_k"])
def test_counter_counts_the_filtered_requests_steps_and_stops(params, request_kw, decode_chunk):
    """A filtered request, then a longer greedy one: the counter takes the
    filtered request's decode steps (its first token is ``jit_first``'s; a
    sweep dispatched ahead of its last token and dropped is in neither
    counter) and nothing once its slot is free."""
    eng = tiny_engine(params, decode_chunk=decode_chunk)
    eng.start()
    try:
        sampled = eng.submit(GenRequest(prompt_tokens=[1, 2, 3], max_new_tokens=9,
                                        temperature=0.7, **request_kw))
        assert len(drain(sampled)) == 9
        mid, retired = (eng.snapshot_stats()[k] for k in ("sampler_filter_steps", "decode_steps"))
        greedy = eng.submit(GenRequest(prompt_tokens=[7, 8, 9], max_new_tokens=24))
        assert len(drain(greedy)) == 24
        values = metrics_of(eng)
    finally:
        eng.stop()
    # 8 decode steps, in sweeps of decode_chunk
    steps = -(-8 // decode_chunk) * decode_chunk
    assert mid == retired == steps
    assert values[NAME] == eng.stats["sampler_filter_steps"] == mid
    assert eng.stats["decode_steps"] - retired >= 23
