"""``ModelConfig.layer_plan``: the one list of layers that every published
spelling of a stack is read into (models/config.py ``_plan``), against the
published totals, the parameter tree's stacks and the cache tree's leaves."""

import copy
import pickle
from collections import Counter

import jax
import pytest

from kserve_vllm_mini_tpu.models.config import ModelConfig, get_config
from kserve_vllm_mini_tpu.models.llama import init_kv_cache, init_params_quantized
from kserve_vllm_mini_tpu.ops.quant import QUANTIZABLE, is_quantized

# preset -> (layers, mixers by kind, feed-forwards by kind, cache leaves by layers)
PUBLISHED = {
    "nemotron-3-super-120b": (88, {"ssm": 40, "attn": 8}, {"latent": 40},
                              {"k": 8, "v": 8, "ssm_state": 40, "ssm_conv": 40}),
    "falcon-h1-34b": (72, {"ssm": 72, "attn": 72}, {"mlp": 72},
                      {"k": 72, "v": 72, "ssm_state": 72, "ssm_conv": 72}),
    "lfm2-8b-a1b": (24, {"conv": 18, "attn": 6}, {"mlp": 2, "glu": 22},
                    {"k": 6, "v": 6, "conv_tail": 18}),
    "smallthinker-21b-a3b": (52, {"attn": 52}, {"glu": 52},
                             {"k": 13, "v": 13, "k_win": 39, "v_win": 39}),
    "olmo-hybrid-7b": (32, {"linear": 24, "attn": 8}, {"mlp": 32},
                       {"k": 8, "v": 8, "lin_state": 24, "lin_conv": 24}),
    "gemma-2-9b": (42, {"attn": 42}, {"mlp": 42}, {"k": 42, "v": 42}),
    "mixtral-8x7b": (32, {"attn": 32}, {"moe": 32}, {"k": 32, "v": 32}),
    "nemotron-h-tiny": (7, {"ssm": 3, "attn": 1}, {"latent": 3},
                        {"k": 1, "v": 1, "ssm_state": 3, "ssm_conv": 3}),
    "falcon-h1-tiny": (3, {"ssm": 3, "attn": 3}, {"mlp": 3},
                       {"k": 3, "v": 3, "ssm_state": 3, "ssm_conv": 3}),
    "lfm2-moe-tiny": (6, {"conv": 4, "attn": 2}, {"mlp": 1, "glu": 5},
                      {"k": 2, "v": 2, "conv_tail": 4}),
    "smallthinker-tiny": (8, {"attn": 8}, {"glu": 8},
                          {"k": 2, "v": 2, "k_win": 6, "v_win": 6}),
    "olmo-hybrid-tiny": (8, {"linear": 6, "attn": 2}, {"mlp": 8},
                         {"k": 2, "v": 2, "lin_state": 6, "lin_conv": 6}),
    "phi-tiny": (2, {"attn": 2}, {"mlp": 2}, {"k": 2, "v": 2}),
}


@pytest.mark.parametrize("preset", PUBLISHED)
def test_the_plan_is_the_published_stack(preset):
    cfg = get_config(preset)
    plan = cfg.layer_plan
    length, mixers, ffns, leaves = PUBLISHED[preset]
    assert len(plan) == cfg.n_layers == length
    assert Counter(m for e in plan for m in e.mixers) == mixers
    assert Counter(e.ffn for e in plan if e.ffn) == ffns
    # every cache leaf's layers are owned once each, in stack order
    owned: dict[str, list[int]] = {leaf: [] for leaf in plan.leaves}
    for e in plan:
        for leaf, i in e.cache:
            owned[leaf].append(i)
        assert e.kv is None or all(leaf in dict(e.cache) for leaf in e.kv)
    assert {leaf: len(at) for leaf, at in owned.items()} == leaves
    assert all(at == list(range(len(at))) for at in owned.values())
    # ... and they are the leaves and depths of the cache tree itself
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, max_seq=64))
    assert {leaf: a.shape[0] for leaf, a in cache.items()} == leaves
    # every layer of every weight stack is some layer's, once
    held: dict[str, list[int]] = {}
    for e in plan:
        if e.mixers:
            held.setdefault(e.stack, []).append(e.index)
        if e.ffn and e.ffn_stack != e.stack:
            held.setdefault(e.ffn_stack, []).append(e.ffn_index)
    assert all(at == list(range(len(at))) for at in held.values())
    if cfg.d_model <= 128:
        layers = jax.eval_shape(
            lambda: init_params_quantized(jax.random.PRNGKey(0), cfg))["layers"]
        stacks = {None: layers} if None in held else layers
        assert set(stacks) == set(held)
        for name, stack in stacks.items():
            depths = {a.shape[0] for a in jax.tree.leaves(stack)}
            assert depths == {len(held[name])}, name
            # the one list of quantizable names meets no leaf that stays as it is
            for leaf, value in stack.items():
                assert is_quantized(value) == (leaf in QUANTIZABLE), leaf
    # the counts the engine and the cache read are sums over it
    assert cfg.n_full_layers == leaves["k"]
    assert cfg.n_window_layers == leaves.get("k_win", 0)
    assert cfg.n_ssm_layers == mixers.get("ssm", 0)
    assert cfg.n_conv_layers == mixers.get("conv", 0)
    assert cfg.n_linear_layers == mixers.get("linear", 0)
    assert cfg.n_expert_layers == ffns.get("glu", 0) + ffns.get("latent", 0)
    assert cfg.is_hybrid == (set(leaves) != {"k", "v"})


def test_the_plan_says_how_each_kind_of_layer_reads():
    small = get_config("smallthinker-tiny").layer_plan
    assert [(e.ring, e.window, e.rope) for e in small[:2]] == [(False, None, False),
                                                               (True, 16, True)]
    assert all(e.route_from_input and e.ffn_norm == "ffn_norm" for e in small)
    block = get_config("falcon-h1-tiny").layer_plan[1]
    assert (block.mixers, block.stack, block.ffn_stack, block.ffn_norm, block.at("ssm_conv"),
            block.at("v")) == (("ssm", "attn"), "parallel", "parallel", "mlp_norm", 1, 1)
    expert = get_config("nemotron-h-tiny").layer_plan[1]
    assert (expert.mixers, expert.kv, expert.ffn_stack, expert.ffn_norm) == (
        (), None, "experts", "norm_in")
    # a config that has built its plan still copies and pickles
    cfg = get_config("smallthinker-tiny")
    for twin in (copy.deepcopy(cfg), pickle.loads(pickle.dumps(cfg))):
        assert twin == cfg and twin.layer_plan == small and twin.layer_plan.leaves == small.leaves
    # gemma's alternation: even layers local, odd layers global
    assert [e.window for e in get_config("gemma-tiny").layer_plan] == [16, None, 16, None]
    assert {e.window for e in get_config("mistral-tiny").layer_plan} == {16}


TINY = dict(name="t", vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64)
EXPERTS = dict(n_routed_experts=4, num_experts_per_tok=2, moe_intermediate_size=16)


@pytest.mark.parametrize("spellings, why", [
    (dict(sliding_window_layout=(0, 1), rope_layout=(0, 1), sliding_window_size=8,
          hybrid_override_pattern="M*", **EXPERTS), "no layer_types,\\s+hybrid_override_pattern"),
    (dict(sliding_window_layout=(0, 1), rope_layout=(0, 1), sliding_window_size=8,
          layer_types=("conv", "full_attention"), **EXPERTS), "no layer_types"),
    (dict(hybrid_override_pattern="**", layer_types=("conv", "full_attention")),
     "takes no layer_types"),
])
def test_two_spellings_of_one_stack_are_refused(spellings, why):
    with pytest.raises(ValueError, match=why):
        ModelConfig(**TINY, **spellings)


@pytest.mark.parametrize("preset, cut", [
    # the benchmark's stages: 12 of 52 layers under the published layouts
    # (n_layers cuts them), 12 of 72 blocks (the pattern IS the depth)
    ("smallthinker-21b-a3b", dict(n_layers=12)),
    ("falcon-h1-34b", dict(hybrid_override_pattern="P" * 12)),
])
def test_a_cut_stack_reads_the_first_entries(preset, cut):
    full, part = get_config(preset), get_config(preset, **cut)
    assert len(part.layer_plan) == part.n_layers == 12
    assert tuple(part.layer_plan) == tuple(full.layer_plan[:12])
    assert part.layer_plan.leaves == full.layer_plan.leaves
