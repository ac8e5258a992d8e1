"""What the comparison of the short-convolution / expert stack with its plain
reference must see (tests/test_conv_moe.py holds the sound program to
TOLERANCE): one case each, every one a program that passes every shape check
and serves fluent tokens, and every one over SEEN. A file of its own so that
the suite's workers share the programs it compiles."""

from functools import partial

import jax
import jax.numpy as jnp
import pytest

from kserve_vllm_mini_tpu.models import llama, moe, short_conv
from tests.test_conv_moe import (SEEN, TOLERANCE, a_sequence, f32_config, off_by, served_weights,
                                 tiny_model, wanted)
from tests.test_hybrid import make_step, prefill_then_decode

# name -> what the program's configuration is changed to
BY_CONFIG = {
    "no_normalisation_over_the_chosen": lambda c: c.scaled(norm_topk_prob=False),
    "routed_scaling_factor_2": lambda c: c.scaled(routed_scaling_factor=2.0),
    "one_expert_a_token_fewer": lambda c: c.scaled(num_experts_per_tok=c.num_experts_per_tok - 1),
    "rope_theta_1e4": lambda c: c.scaled(rope_theta=1e4),
    "rms_eps_1e-3": lambda c: c.scaled(rms_eps=1e-3),
    "w8a8": lambda c: c.scaled(quant_mode="w8a8"),
}
# name -> what the program's weights are changed to (the reference keeps its own)
BY_WEIGHTS = ["taps_reversed", "expert_bias_dropped_from_the_choice", "no_dense_mlp_under_layer_0",
              "head_not_tied", "q_and_k_gains_swapped"]
BY_PATCH = ["no_B_gate", "no_C_gate", "B_and_C_swapped", "silu_inside_the_convolution",
            "no_per_head_qk_norm", "qk_norm_over_the_whole_projection",
            "expert_bias_weighs_too", "softmax_router", "pad_in_the_tail", "tail_not_reset"]


def _with_in_proj_streams(fix):
    """``short_conv.linear`` with the three streams of ``in_proj``'s output
    (B | C | x) handed to ``fix`` and put together again."""
    linear = short_conv.linear

    def patched(x, w, mode="dequant"):
        y = linear(x, w, mode=mode)
        if y.shape[-1] != 3 * x.shape[-1]:
            return y                                       # out_proj
        return jnp.concatenate(fix(*jnp.split(y, 3, axis=-1)), axis=-1)

    return patched


def _route_weighing_by_the_bias(x, router, bias, cfg, norm_eps=moe.NORM_EPS_LATENT):
    """``route_sigmoid`` with the selection bias left in the weights."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST)) + bias
    w, chosen = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps), chosen


def _route_softmax(x, router, bias, cfg, norm_eps=moe.NORM_EPS_LATENT):
    scores = jax.nn.softmax(jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, chosen = jax.lax.top_k(scores + bias, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps), chosen


@pytest.mark.parametrize("broken", list(BY_CONFIG) + BY_WEIGHTS + BY_PATCH)
def test_the_comparison_sees_what_it_must(broken, monkeypatch):
    """Each of these passes every shape check and serves fluent tokens; each
    moves the logits past the comparison's tolerance with room (``SEEN``; the
    readings are beside ``TOLERANCE`` in tests/test_conv_moe.py)."""
    m = tiny_model()
    seq = a_sequence(m, 70, 5)
    want = wanted(7, m, [seq])[0]
    params, cfg, valid, pieces = served_weights(7, m), f32_config(m), 50, None
    layers = params["layers"]
    if broken in BY_CONFIG:
        cfg = BY_CONFIG[broken](cfg)
    elif broken == "taps_reversed":
        layers["conv"]["conv"] = layers["conv"]["conv"][:, ::-1]
    elif broken == "expert_bias_dropped_from_the_choice":
        layers["experts"]["router_bias"] = jnp.zeros_like(layers["experts"]["router_bias"])
    elif broken == "no_dense_mlp_under_layer_0":
        down = layers["mlp"]["w_down"]
        layers["mlp"]["w_down"] = {**down, "q": jnp.zeros_like(down["q"])}
    elif broken == "head_not_tied":
        # an untied head that is another matrix of the same draw (the embedding's
        # rows in reverse order): every shape the same
        cfg = cfg.scaled(tie_embeddings=False)
        params["lm_head"] = params["embed"][::-1]
    elif broken == "q_and_k_gains_swapped":
        layers["attn"]["q_norm"], layers["attn"]["k_norm"] = (
            layers["attn"]["k_norm"], layers["attn"]["q_norm"])
    elif broken == "no_B_gate":
        monkeypatch.setattr(short_conv, "linear", _with_in_proj_streams(
            lambda b, c, u: (jnp.ones_like(b), c, u)))
    elif broken == "no_C_gate":
        monkeypatch.setattr(short_conv, "linear", _with_in_proj_streams(
            lambda b, c, u: (b, jnp.ones_like(c), u)))
    elif broken == "B_and_C_swapped":
        monkeypatch.setattr(short_conv, "linear", _with_in_proj_streams(
            lambda b, c, u: (c, b, u)))
    elif broken == "silu_inside_the_convolution":
        conv = short_conv.causal_conv
        monkeypatch.setattr(short_conv, "causal_conv", lambda *a, **k: conv(
            *a, **{**k, "activation": jax.nn.silu}))
    elif broken in ("no_per_head_qk_norm", "qk_norm_over_the_whole_projection"):
        # qkv_proj norms [B, heads, T, head_dim] with a gain [head_dim]; the
        # layers' own norms have a gain of the hidden size
        norm, hd = llama.rms_norm, cfg.head_dim

        def head_norm(x, gain, eps):
            if gain.shape[-1] != hd:
                return norm(x, gain, eps)
            if broken == "no_per_head_qk_norm":
                return x
            rows = x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)
            rows = norm(rows, jnp.tile(gain, x.shape[1]), eps)
            return rows.reshape(x.shape[0], x.shape[2], x.shape[1], hd).transpose(0, 2, 1, 3)

        monkeypatch.setattr(llama, "rms_norm", head_norm)
    elif broken == "expert_bias_weighs_too":
        monkeypatch.setattr(moe, "route_sigmoid", _route_weighing_by_the_bias)
    elif broken == "softmax_router":
        monkeypatch.setattr(moe, "route_sigmoid", _route_softmax)
    elif broken == "pad_in_the_tail":
        valid = 64                          # the 14 pad positions are taken for real
    elif broken == "tail_not_reset":
        # the second piece starts from an empty tail, as a fresh prefill does
        pieces = [32, 18]
        mixer = short_conv.short_conv_mixer
        monkeypatch.setattr(short_conv, "short_conv_mixer", lambda p, c, x, tail, v: mixer(
            p, c, x, jnp.zeros_like(tail) if x.shape[1] > 1 else tail, v))
    got, _ = prefill_then_decode(params, cfg, seq, 50, 64, valid=valid, pieces=pieces,
                                 step=make_step())
    assert off_by(got[:-1], want) > SEEN, broken


def test_the_sound_program_under_the_same_patches_machinery_agrees():
    """The control of the controls: the same calls, nothing broken, agree."""
    m = tiny_model()
    seq = a_sequence(m, 70, 5)
    want = wanted(7, m, [seq])[0]
    for pieces in (None, [32, 18]):
        got, _ = prefill_then_decode(served_weights(7, m), f32_config(m), seq, 50, 64,
                                     pieces=pieces, step=make_step())
        assert off_by(got[:-1], want) < TOLERANCE
