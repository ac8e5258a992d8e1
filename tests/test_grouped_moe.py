"""The grouped dispatch of a long piece (models/moe.py ``_run_grouped`` over
ops/grouped_matmul.py) against the dense dispatch it stands beside: the same
sum up to the order of a float32 addition, at any routing, with no capacity
and no drop. Tiny shapes, the kernel in interpret mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models import moe
from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.ops import grouped_matmul as gm
from kserve_vllm_mini_tpu.ops.quant import quantize_weight

L, E, K, D, F = 3, 4, 2, 128, 256
BODIES = {"glu_silu": moe.GLU_SILU, "glu_relu": moe.GLU_RELU, "latent_relu2": moe.LATENT_RELU2}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Row tiles of 8: a piece of 9 tokens or more is grouped, and a 40-token
    piece has groups of several tiles."""
    monkeypatch.setattr(gm, "ROW_TILE", 8)


def stacks_of(body, quantized, dtype=jnp.float32, experts=E, seed=0):
    """The body's layer stacks [L, experts, ., .], plain or int8 leaves."""
    first, _, last = body
    shapes = {**{n: (L, experts, D, F) for n in first}, last: (L, experts, F, D)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, shapes.items()):
        w = (jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5).astype(dtype)
        out[name] = quantize_weight(w) if quantized else w
    return out


def piece(tokens, seed=1, dtype=jnp.float32):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (tokens, D), jnp.float32).astype(dtype)
    w = jax.random.uniform(kw, (tokens, K), jnp.float32, 0.1, 1.0)
    return x, w


def random_choice(tokens, experts=E, seed=2):
    """K distinct experts a token."""
    scores = jax.random.uniform(jax.random.PRNGKey(seed), (tokens, experts))
    return jax.lax.top_k(scores, K)[1]


def both(x, w, chosen, p, body, qm="dequant", offset=0, layer=1):
    dense, landed = moe._run_held(x, w, chosen, offset, p, body, qm, "off", layer)
    grouped, landed_g = moe._run_held(x, w, chosen, offset, p, body, qm, "interpret", layer)
    assert np.array_equal(landed, landed_g)
    return np.asarray(dense), np.asarray(grouped)


@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_grouped_dispatch_is_the_dense_one(body, quantized):
    """The three expert bodies over plain and int8 stacks, layer 1 of a stack
    of 3: float32 activations, so what differs is the order of the sums (and,
    for int8, a scale applied in float32 to the product either way)."""
    p = stacks_of(BODIES[body], quantized)
    x, w = piece(40)
    dense, grouped = both(x, w, random_choice(40), p, BODIES[body])
    assert dense.shape == grouped.shape == (40, D)
    assert np.abs(dense).max() > 0.1
    np.testing.assert_allclose(grouped, dense, rtol=1e-4, atol=1e-4)


def test_bf16_activations_over_int8_stacks_agree_to_their_rounding():
    """The serving precision: the kernel scales and activates in float32 and
    rounds once, the dense dispatch rounds each product to bfloat16."""
    p = stacks_of(moe.GLU_SILU, True)
    x, w = piece(40, dtype=jnp.bfloat16)
    dense, grouped = both(x, w, random_choice(40), p, moe.GLU_SILU)
    assert np.abs(grouped - dense).max() <= 0.03 * np.abs(dense).max()


ROUTINGS = {
    # every token on one expert (twice its K: the second choice is another,
    # as a top-k's are distinct): its group is ten tiles, two experts' are empty
    "all_on_one": lambda n: jnp.stack([jnp.full((n,), 2), jnp.full((n,), 0)], axis=1),
    # experts 1 and 3 get nothing
    "two_empty": lambda n: jnp.stack([jnp.zeros((n,), jnp.int32), jnp.full((n,), 2)], axis=1),
    # 37 tokens: groups of 8 k + 5 rows and the like break across tiles
    "ragged": lambda n: random_choice(n, seed=5),
}


@pytest.mark.parametrize("routing,tokens", [("all_on_one", 40), ("two_empty", 40), ("ragged", 37)])
def test_no_routing_drops_a_token(routing, tokens):
    p = stacks_of(moe.GLU_SILU, True)
    x, w = piece(tokens)
    dense, grouped = both(x, w, ROUTINGS[routing](tokens).astype(jnp.int32), p, moe.GLU_SILU)
    np.testing.assert_allclose(grouped, dense, rtol=1e-4, atol=1e-4)
    assert (np.abs(grouped).sum(axis=1) > 0).all()


@pytest.mark.parametrize("offset", [0, 4, 2])
def test_experts_held_from_an_offset(offset):
    """A device that holds experts ``offset .. offset + 4`` of 8: the
    assignments that land elsewhere are dropped from the groups and from
    nothing else, so the shares of two devices add up to the whole."""
    body, routed = moe.GLU_RELU, 8
    whole = stacks_of(body, True, experts=routed)
    x, w = piece(40)
    chosen = random_choice(40, experts=routed, seed=7)

    def share(o):
        return both(x, w, chosen, jax.tree_util.tree_map(lambda a: a[:, o:o + 4], whole),
                    body, offset=o)

    dense, grouped = share(offset)
    np.testing.assert_allclose(grouped, dense, rtol=1e-4, atol=1e-4)
    if offset == 4:     # with the first half's share (its own case above): the uncut layer
        all_held = moe._run_held(x, w, chosen, 0, whole, body, "dequant", "off", 1)[0]
        np.testing.assert_allclose(share(0)[1] + grouped, all_held, rtol=1e-4, atol=1e-4)


def test_a_token_none_of_whose_experts_is_held_gets_zero():
    p = stacks_of(moe.GLU_SILU, False)
    x, w = piece(40)
    chosen = jnp.full((40, K), 9, jnp.int32).at[:3].set(jnp.array([0, 1]))
    dense, grouped = both(x, w, chosen, p, moe.GLU_SILU)
    assert (grouped[3:] == 0).all() and np.isfinite(grouped).all()
    np.testing.assert_allclose(grouped, dense, rtol=1e-4, atol=1e-4)


def grouped_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("_run_grouped")


@pytest.mark.parametrize("tokens,calls", [(8, 0), (9, 1)])
def test_the_piece_length_chooses_the_dispatch(tokens, calls):
    """A row tile of tokens runs every expert (a decode sweep, a short
    piece); one more takes the grouped dispatch."""
    p = stacks_of(moe.GLU_SILU, True)
    x, w = piece(tokens)
    run = lambda x, w, c, p: moe._run_held(x, w, c, 0, p, moe.GLU_SILU, "dequant",
                                           "interpret", 0)[0]
    assert grouped_calls(run, x, w, random_choice(tokens), p) == calls


@pytest.mark.parametrize("why,kernels,qm,quantized,width", [
    ("w8a8 contracts in int8: correct's control", "interpret", "w8a8", True, D),
    ("no kernels: the CPU, or a mesh", "off", "dequant", True, D),
    ("a width that is no whole lane tile", "interpret", "dequant", False, 96),
])
def test_what_keeps_a_long_piece_on_the_dense_dispatch(why, kernels, qm, quantized, width):
    p = stacks_of(moe.GLU_SILU, quantized)
    if width != D:
        p = {"w_gate": p["w_gate"][..., :width, :], "w_up": p["w_up"][..., :width, :],
             "w_down": p["w_down"][..., :width]}
    x, w = piece(40)
    run = lambda x, w, c, p: moe._run_held(x[:, :width], w, c, 0, p, moe.GLU_SILU, qm,
                                           kernels, 0)[0]
    assert grouped_calls(run, x, w, random_choice(40), p) == 0, why


def test_a_walk_of_expert_layers_lowers_the_dispatch_once():
    """Three layers of one stack behind one jit: one body in the lowered
    module, called three times (each call site lowered on its own is what
    made an earlier attempt's set-up 14 s longer)."""
    p = stacks_of(moe.GLU_SILU, True)
    x, w = piece(40)

    def walk(x, w, chosen, p):
        for layer in range(L):
            x = x + moe._run_held(x, w, chosen, 0, p, moe.GLU_SILU, "dequant",
                                  "interpret", layer)[0]
        return x

    text = jax.jit(walk).lower(x, w, random_choice(40), p).as_text()
    assert text.count("func.func private @_run_grouped") == 1
    assert text.count("call @_run_grouped") == L


def test_the_three_layers_take_it_through_their_blocks():
    """``moe_glu_block`` handed the layer stack and an index, ``moe_mlp`` a
    layer's own leaves (as ``moe_latent_block`` is: the latent body is held
    above): each the dense dispatch's result."""
    cfg = get_config("lfm2-moe-tiny", dtype="float32")
    held = cfg.experts_held
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 40, D), jnp.float32)
    p = stacks_of(moe.GLU_SILU, True, experts=held)
    p["router"] = jax.random.normal(jax.random.PRNGKey(4), (L, D, cfg.n_routed_experts))
    p["router_bias"] = jnp.zeros((L, cfg.n_routed_experts))
    dense, s0 = moe.moe_glu_block(p, cfg, h, layer=2)
    stacked, s1 = moe.moe_glu_block(p, cfg, h, kernels="interpret", layer=2)
    np.testing.assert_allclose(stacked, dense, rtol=1e-4, atol=1e-4)
    assert np.array_equal(s0, s1)

    mixtral = get_config("mixtral-tiny", dtype="float32")
    q = jax.tree_util.tree_map(lambda a: a[0], stacks_of(moe.GLU_SILU, False, experts=mixtral.n_experts))
    q["router"] = jax.random.normal(jax.random.PRNGKey(5), (D, mixtral.n_experts))
    np.testing.assert_allclose(moe.moe_mlp(q, mixtral, h, "interpret"), moe.moe_mlp(q, mixtral, h),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("counts", [(0, 0, 0), (40, 0, 0), (7, 8, 9), (1, 16, 0)])
def test_group_rows_lays_every_assignment_out_once(counts):
    """Whole tiles a group, in order; padding and the assignments of no
    group past the used tiles; ``row`` the inverse of ``source``."""
    tile, groups = 8, len(counts)
    expert = np.concatenate([np.full(c, g) for g, c in enumerate(counts)] + [np.full(5, groups)])
    expert = np.random.default_rng(0).permutation(expert).astype(np.int32)
    source, tiles, row = map(np.asarray, gm.group_rows(jnp.asarray(expert), groups, tile))
    n = len(expert)
    assert source.shape == (-(-(n + groups * (tile - 1)) // tile) * tile,)
    want_tiles = [g for g, c in enumerate(counts) for _ in range(-(-c // tile))]
    assert tiles[0] == len(want_tiles) and list(tiles[1:1 + tiles[0]]) == want_tiles
    assert (tiles[1 + tiles[0]:] == groups).all()
    assert np.array_equal(source[row], np.arange(n))
    for a in range(n):
        used = row[a] < tiles[0] * tile
        assert used == (expert[a] < groups)
        if used:
            assert tiles[1 + row[a] // tile] == expert[a]
    real = source[:tiles[0] * tile]
    assert sorted(real[real < n]) == sorted(np.flatnonzero(expert < groups))


@pytest.mark.parametrize("n_in,n_out,w_dtype,n_w,want", [
    (2048, 1792, "int8", 2, (128, 1792)),     # lfm2's gate and up
    (1792, 2048, "int8", 1, (128, 2048)),     # its down projection
    (2560, 768, "int8", 2, (128, 768)),       # SmallThinker's
    (768, 2560, "bfloat16", 1, (128, 2560)),
    (4096, 14336, "int8", 2, (128, 1792)),    # Mixtral's: the width in blocks
    (2048, 1792, "float32", 2, None),         # neither int8 nor the rows' dtype
    (2048, 1000, "int8", 1, None),            # no whole lane tiles
    (2040, 1792, "int8", 1, None),            # no whole int8 sublane tiles
])
def test_the_block_rule(n_in, n_out, w_dtype, n_w, want, monkeypatch):
    monkeypatch.setattr(gm, "ROW_TILE", 128)
    assert gm.grouped_matmul_block(n_in, n_out, w_dtype, jnp.bfloat16, n_w) == want
