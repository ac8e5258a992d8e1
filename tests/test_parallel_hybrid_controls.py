"""What the comparison of the two-mixer stack with its plain reference must
see (tests/test_parallel_hybrid.py holds the sound program to TOLERANCE): one
case each, every one a program that passes every shape check and serves fluent
tokens, and every one over SEEN. A file of its own so that the suite's workers
share the two dozen programs it compiles."""

import jax
import jax.numpy as jnp
import pytest

from kserve_vllm_mini_tpu.models import llama, mamba
from kserve_vllm_mini_tpu.models.config import ModelConfig
from tests.test_hybrid import make_step, prefill_then_decode
from tests.test_parallel_hybrid import (SEEN, TOLERANCE, a_sequence, f32_config, off_by, reference,
                                        served_weights, tiny_model)


def _swap_z_and_x(cfg):
    by = cfg.ssm_multipliers
    return cfg.scaled(ssm_multipliers=(by[1], by[0], *by[2:]))


def _without(cfg, i):
    by = list(cfg.ssm_multipliers)
    by[i] = 1.0
    return cfg.scaled(ssm_multipliers=tuple(by))


# name -> what the program is changed to; each a ModelConfig the sound model is NOT
BY_CONFIG = {
    "no_ssm_in_multiplier": lambda c: c.scaled(ssm_in_multiplier=1.0),
    "no_ssm_multiplier_z": lambda c: _without(c, 0),
    "no_ssm_multiplier_x": lambda c: _without(c, 1),
    "no_ssm_multiplier_B": lambda c: _without(c, 2),
    "no_ssm_multiplier_C": lambda c: _without(c, 3),
    "no_ssm_multiplier_dt": lambda c: _without(c, 4),
    "ssm_multipliers_z_x_swapped": _swap_z_and_x,
    "no_ssm_out_multiplier": lambda c: c.scaled(ssm_out_multiplier=1.0),
    "no_attention_in_multiplier": lambda c: c.scaled(attention_in_multiplier=1.0),
    "no_attention_out_multiplier": lambda c: c.scaled(attention_out_multiplier=1.0),
    "no_key_multiplier": lambda c: c.scaled(key_multiplier=1.0),
    "no_mlp_gate_multiplier": lambda c: c.scaled(mlp_multipliers=(1.0, c.mlp_multipliers[1])),
    "no_mlp_down_multiplier": lambda c: c.scaled(mlp_multipliers=(c.mlp_multipliers[0], 1.0)),
    "no_embedding_multiplier": lambda c: c.scaled(embedding_multiplier=1.0),
    "no_lm_head_multiplier": lambda c: c.scaled(lm_head_multiplier=1.0),
    "rope_theta_1e4": lambda c: c.scaled(rope_theta=1e4),
    "w8a8": lambda c: c.scaled(quant_mode="w8a8"),
}
BY_PATCH = ["no_rope", "norm_before_gate", "no_conv_bias", "bf16_state", "pad_in_state",
            "attention_on_residual_not_normed_input", "mixers_sequential_not_parallel"]


@pytest.mark.parametrize("broken", list(BY_CONFIG) + BY_PATCH)
def test_the_comparison_sees_what_it_must(broken, monkeypatch):
    """Each of these passes every shape check and serves fluent tokens; each
    moves the logits past the comparison's tolerance with room (``SEEN``)."""
    m = tiny_model()
    seq = a_sequence(m, 70, 5)
    want = reference.logits_after(7, m, [seq], [1], 128, 64)[0]
    params, cfg, valid = served_weights(7, m), f32_config(m), 50
    if broken in BY_CONFIG:
        cfg = BY_CONFIG[broken](cfg)
    elif broken == "no_rope":
        monkeypatch.setattr(ModelConfig, "rotary_dim", property(lambda self: 0))
    elif broken == "norm_before_gate":
        monkeypatch.setattr(mamba, "gated_norm", lambda y, z, gain, groups, eps: (
            mamba.grouped_rms_norm(y, gain, groups, eps) * jax.nn.silu(z.astype(jnp.float32))))
    elif broken == "no_conv_bias":
        bias = params["layers"]["parallel"]["conv_b"]
        params["layers"]["parallel"]["conv_b"] = jnp.zeros_like(bias)
    elif broken == "bf16_state":
        one, many = mamba.recurrent_step, mamba.chunked_scan

        def rounded(fn):
            return lambda *a, **k: (lambda out: (
                out[0].astype(jnp.bfloat16).astype(jnp.float32), out[1]))(fn(*a, **k))

        monkeypatch.setattr(mamba, "recurrent_step", rounded(one))
        monkeypatch.setattr(mamba, "chunked_scan", rounded(many))
    elif broken == "pad_in_state":
        valid = 64                          # the 14 pad positions are taken for real
    elif broken in ("attention_on_residual_not_normed_input", "mixers_sequential_not_parallel"):
        # what attention is handed in place of the block's normed input: the
        # residual stream itself, or the norm of the stream AFTER the
        # state-space mixer's output has joined it. A block calls rms_norm (its
        # input norm), the mixer, then qkv_proj: each wrapper records for the next.
        norm, mixer, qkv, seen = llama.rms_norm, mamba.mamba_mixer, llama.qkv_proj, {}

        def recording_norm(x, gain, eps):
            seen["x"] = x
            return norm(x, gain, eps)

        def recording_mixer(p, c, h, *a, **k):
            out = mixer(p, c, h, *a, **k)
            seen["mixed"] = out[0] * c.ssm_out_multiplier
            return out

        def misfed_qkv(p, c, h, *a, **k):
            if broken == "attention_on_residual_not_normed_input":
                return qkv(p, c, seen["x"], *a, **k)
            return qkv(p, c, norm(seen["x"] + seen["mixed"], p["norm_in"], c.rms_eps), *a, **k)

        monkeypatch.setattr(llama, "rms_norm", recording_norm)
        monkeypatch.setattr(mamba, "mamba_mixer", recording_mixer)
        monkeypatch.setattr(llama, "qkv_proj", misfed_qkv)
    got, _ = prefill_then_decode(params, cfg, seq, 50, 64, valid=valid, step=make_step())
    assert off_by(got[:-1], want) > SEEN, broken


def test_the_sound_program_under_the_same_patches_machinery_agrees():
    """The control of the controls: the same call, nothing broken, agrees."""
    m = tiny_model()
    seq = a_sequence(m, 70, 5)
    want = reference.logits_after(7, m, [seq], [1], 128, 64)[0]
    got, _ = prefill_then_decode(served_weights(7, m), f32_config(m), seq, 50, 64, step=make_step())
    assert off_by(got[:-1], want) < TOLERANCE
