"""A hybrid stack in the engine: streams under continuous batching, prefill
in pieces and slot reuse; a parked or mid-prefill slot's state through its
neighbours' sweeps; and every setting a state without positions refuses."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import forward, init_params
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest

# one whole period (3 linear + 1 full) of the tiny preset: half the compile time
_FULL = get_config("olmo-hybrid-tiny", dtype="float32")
TINY = _FULL.scaled(n_layers=4, layer_types=_FULL.layer_types[:4])


def make_engine(**ecfg):
    params = init_params(jax.random.PRNGKey(0), TINY)
    kw = dict(max_slots=4, max_seq_len=256, max_prefill_len=128)
    return params, Engine(params, TINY, EngineConfig(**{**kw, **ecfg}))


def run_requests(eng, prompts, max_new=10):
    hs = [eng.submit(GenRequest(prompt_tokens=p, max_new_tokens=max_new, temperature=0.0,
                                eos_id=None)) for p in prompts]
    outs = []
    for h in hs:
        toks = []
        while True:
            ev = h.events.get(timeout=300)
            if ev[0] != "token":
                break
            toks.append(ev[1])
        outs.append(toks)
    return outs


@jax.jit
def _padded_forward(params, toks, n):
    return forward(params, TINY, toks, jnp.arange(toks.shape[1])[None], valid_len=n)[0]


def greedy(params, seq, n_prompt):
    """The cache-free forward's own continuation, every sequence padded to
    one length so that one program serves them all."""
    toks = jnp.asarray([seq + [0] * (128 - len(seq))], jnp.int32)
    lg = _padded_forward(params, toks, jnp.asarray([len(seq)], jnp.int32))
    return [int(jnp.argmax(lg[0, i])) for i in range(n_prompt - 1, len(seq) - 1)]


@pytest.mark.parametrize("ecfg", [{}, {"prefill_chunk": 32}, {"decode_chunk": 4},
                                  {"decode_pipeline": False, "prefill_chunk": 16},
                                  {"kv_cache_dtype": "int8"}],
                         ids=["plain", "pieces32", "fused4", "sync_pieces16", "int8kv"])
def test_engine_streams_are_the_models_own_under_batching_and_slot_reuse(ecfg):
    """Six requests on four slots: admissions between decode sweeps, prompts
    in pieces, slots reused. Every stream is the cache-free forward's greedy
    continuation, which it is not if a neighbour's sweep, a pad position or
    a previous occupant reaches a state."""
    params, eng = make_engine(**ecfg)
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(3, 500, n)] for n in (70, 23, 100, 45, 17, 90)]
    try:
        outs = run_requests(eng, prompts)
    finally:
        eng.stop()
    agree = [o == greedy(params, p + o, len(p)) for p, o in zip(prompts, outs)]
    if ecfg.get("kv_cache_dtype") == "int8":
        assert sum(agree) >= 4          # int8 K/V may flip a near-tie
    else:
        assert all(agree)
    s = eng.snapshot_stats()
    assert s["linear_state_resets"] == 6 and s["linear_state_slot_steps"] > 0
    assert 0 < s["linear_state_held_slot_steps"] < s["linear_state_slot_steps"]
    assert s["linear_scan_chunks"] >= 6 and s["cache_bytes"]["linear_state"] == 3 * 4 * 4 * 24 * 48 * 4
    assert s["cache_bytes"]["conv_state"] > 0 and s["cache_bytes"]["kv"] > 0


@pytest.mark.parametrize("ecfg", [{}, {"prefill_chunk": 32}, {"decode_chunk": 4}],
                         ids=["plain", "pieces32", "fused4"])
def test_engine_streams_with_the_state_updated_by_the_decode_kernel(ecfg):
    """The same six requests with the engine's decode steps through
    ``kvmini_linear_decode`` (interpreted: what a TPU backend makes of the
    engine's "auto"; nothing in the program picks the interpreter). The state
    is updated inside the cache's stack, parked and mid-prefill rows held by
    the kernel's select: every stream is still the cache-free forward's, and
    the gauge says which program ran."""
    params, eng = make_engine(**ecfg)
    eng._kernels = "interpret"
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(3, 500, n)] for n in (70, 23, 100, 45, 17, 90)]
    try:
        outs = run_requests(eng, prompts)
    finally:
        eng.stop()
    assert all(o == greedy(params, p + o, len(p)) for p, o in zip(prompts, outs))
    s = eng.snapshot_stats()
    assert s["linear_state_kernel"] == 1
    assert 0 < s["linear_state_held_slot_steps"] < s["linear_state_slot_steps"]
    _, eager = make_engine(**ecfg)
    eager.start()
    eager.stop()
    assert eager.snapshot_stats()["linear_state_kernel"] == 0     # "auto" on a CPU


@pytest.mark.parametrize("sampled,kernels", [(False, ""), (True, ""), (False, "interpret")],
                         ids=["greedy", "sampled", "greedy_decode_kernel"])
def test_admissions_behind_the_sweep_in_flight_leave_every_state_its_own(sampled, kernels):
    """Callers admitted while a sweep is in flight, without a drain
    (docs/DECODE_PIPELINE.md): the sweep holds a parked row's state and conv
    tail and moves those of the caller it was dispatched for; the fresh piece,
    later on the device, starts both from zero. Token for token the
    synchronous scheduler's streams, through the jnp update and through
    ``kvmini_linear_decode`` in the cache's stack."""
    from tests.admit_behind import check_stack

    check_stack(make_engine, greedy, sampled, kernels)


def test_a_parked_or_mid_prefill_slot_keeps_its_state_through_neighbours_sweeps():
    params, eng = make_engine(prefill_chunk=32)
    from kserve_vllm_mini_tpu.runtime.tracing import SchedLedger

    eng._sched = SchedLedger()
    rng = np.random.default_rng(1)
    long_p = [int(t) for t in rng.integers(3, 500, 100)]
    short_p = [int(t) for t in rng.integers(3, 500, 20)]
    a = eng.submit(GenRequest(prompt_tokens=short_p, max_new_tokens=40, temperature=0.0, eos_id=None))
    eng._schedule_once()                       # admit the short one: it decodes from now on
    slot_a = next(i for i, h in enumerate(eng._slot_req) if h is a)
    eng.submit(GenRequest(prompt_tokens=long_p, max_new_tokens=4, temperature=0.0, eos_id=None))
    eng._schedule_once()                       # admit the long one: first piece only
    slot_b = next(i for i, st in enumerate(eng._slot_prefill) if st is not None)
    parked = next(i for i in range(4) if i not in (slot_a, slot_b))
    mark = eng._cache["lin_state"].at[:, parked].set(3.25)
    eng._cache = {**eng._cache, "lin_state": mark}
    before_b = np.asarray(eng._cache["lin_state"][:, slot_b])
    before_a = np.asarray(eng._cache["lin_state"][:, slot_a])
    assert np.abs(before_b).max() > 0
    # decode sweeps alone: no further piece of the long prompt
    for _ in range(3):
        eng._dispatch_plain(eng._decode_active())
        eng._retire_one()
    assert np.array_equal(np.asarray(eng._cache["lin_state"][:, slot_b]), before_b)
    assert bool((eng._cache["lin_state"][:, parked] == 3.25).all())
    assert not np.array_equal(np.asarray(eng._cache["lin_state"][:, slot_a]), before_a)
    # a fresh prefill into the marked slot starts from zero whatever it held
    eng._free = [parked]
    c = eng.submit(GenRequest(prompt_tokens=short_p, max_new_tokens=3, temperature=0.0, eos_id=None))
    while c.finish_reason is None:
        eng._schedule_once()
    assert c.tokens == greedy(params, short_p + c.tokens, len(short_p))
    assert eng.stats["linear_state_held_slot_steps"] > 0


def _drafter():
    cfg = get_config("llama-tiny")
    return init_params(jax.random.PRNGKey(1), cfg), cfg


REFUSED = {
    "prefix_cache": (dict(prefix_cache=True), {}, "prefix_cache"),
    "paged": (dict(kv_layout="paged"), {}, "kv_layout"),
    "drafter": ({}, {"drafter": _drafter}, "drafter"),
    "spec_tokens": (dict(spec_tokens=3), {}, "spec_tokens"),
    "disagg": (dict(disagg=True), {}, "disagg"),
    "lora": ({}, {"lora": lambda: {"layers": {}, "names": {}}}, "LoRA"),
    "host_tier": (dict(kv_host_tier_bytes=1 << 20), {}, "kv_host_tier_bytes"),
    "mesh": ({}, {"mesh": lambda: jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tp",))},
             "mesh"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_engine_refuses_what_a_state_without_positions_cannot_do(what):
    ecfg, extra, names = REFUSED[what]
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), TINY))
    with pytest.raises(ValueError, match="hybrid stack.*" + names):
        Engine(params, TINY, EngineConfig(max_slots=2, max_seq_len=64, **ecfg),
               **{k: v() for k, v in extra.items()})


def test_runtime_adapter_load_is_refused_too():
    _, eng = make_engine()
    err = eng.load_adapter("a", {"wq": (jnp.zeros((4, 128, 2)), jnp.zeros((4, 2, 128)))})
    assert err and "hybrid" in err


def test_a_homogeneous_engine_hands_its_executor_no_valid_length():
    """Only a hybrid stack's steps pass `valid_len`: the executor of a pp mesh
    (`parallel/serving_pp.py pp_forward`, in `Engine._fwd`'s place) takes none,
    and every other model ignores it."""
    cfg = get_config("llama-tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64,
                                           prefill_chunk=16))
    seen = []

    def executor(*args, **kw):
        seen.append(set(kw))
        return forward(*args, **kw)

    eng._fwd = executor
    eng.start()
    try:
        out, = run_requests(eng, [list(range(3, 43))], max_new=3)
    finally:
        eng.stop()
    assert len(out) == 3 and len(seen) >= 3        # a prefill, a later piece, a decode step
    assert not any("valid_len" in kw for kw in seen)
