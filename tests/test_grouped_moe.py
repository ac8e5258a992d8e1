"""The grouped dispatch of a long piece (models/moe.py ``_run_grouped`` over
ops/grouped_matmul.py) and the sweep of a decode step's touched experts
(``expert_sweep``) against the dense dispatch they stand beside: the same sum
up to the order of a float32 addition, at any routing, with no capacity and
no drop. Tiny shapes, the kernels in interpret mode.
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
    piece has groups of several tiles. A sweep of up to 6 rows: 7 and 8 rows
    are what 33-128 are at the kernels' own sizes, dense."""
    monkeypatch.setattr(gm, "ROW_TILE", 8)
    monkeypatch.setattr(gm, "SWEEP_ROWS", 6)


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


def both(x, w, chosen, p, body, qm="dequant", offset=0, layer=1, real=None):
    """The dense dispatch's result and the kernels'. What each visited: every
    held expert; the experts a (live) row chose."""
    dense, landed, all_held = moe._run_held(x, w, chosen, offset, p, body, qm, "off", layer, real)
    kernel, landed_k, visited = moe._run_held(x, w, chosen, offset, p, body, qm, "interpret",
                                              layer, real)
    assert np.array_equal(landed, landed_k)
    assert all_held == landed.shape[1]
    live = np.asarray(landed) if real is None or x.shape[0] > gm.SWEEP_ROWS else (
        np.asarray(landed) & np.asarray(real)[:, None])
    assert visited == live.any(axis=0).sum()
    return np.asarray(dense), np.asarray(kernel)


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


def grouped_calls(fn, *args, name="_run_grouped"):
    return str(jax.make_jaxpr(fn)(*args)).count(name)


@pytest.mark.parametrize("tokens,grouped,swept", [(6, 0, 1), (7, 0, 0), (8, 0, 0), (9, 1, 0)])
def test_the_piece_length_chooses_the_dispatch(tokens, grouped, swept):
    """A decode step's few rows sweep the touched experts; more, up to a row
    tile of tokens, run every expert; one more takes the grouped dispatch."""
    p = stacks_of(moe.GLU_SILU, True)
    x, w = piece(tokens)
    run = lambda x, w, c, p: moe._run_held(x, w, c, 0, p, moe.GLU_SILU, "dequant",
                                           "interpret", 0)[0]
    assert grouped_calls(run, x, w, random_choice(tokens), p) == grouped
    assert grouped_calls(run, x, w, random_choice(tokens), p, name="expert_sweep") == swept


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
    # and a decode step's sweep
    text = jax.jit(walk).lower(x[:6], w[:6], random_choice(6), p).as_text()
    assert text.count("func.func private @expert_sweep") == 1
    assert text.count("call @expert_sweep") == L


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
    assert np.array_equal(s0[:3], s1[:3]) and s0[3] == held and s1[3] == s1[1]

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


# -- a decode step: the touched experts swept, the others never fetched ----------

ROWS = 6    # a decode step's rows under the fixture's SWEEP_ROWS


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_a_decode_step_sweeps_what_the_dense_dispatch_sums(body, quantized, dtype):
    """The three bodies over plain stacks of the rows' dtype and int8 leaves:
    float32 rows differ by the order of the sums, bfloat16 rows by the dense
    dispatch's rounding of each product (the prefill cases' tolerances).
    ``both`` holds ``visited`` to the touched experts, and to every held one
    on the dense side."""
    dt = jnp.dtype(dtype)
    p = stacks_of(BODIES[body], quantized, dt)
    x, w = piece(ROWS, dtype=dt)
    dense, swept = both(x, w, random_choice(ROWS), p, BODIES[body])
    assert dense.shape == swept.shape == (ROWS, D) and np.abs(dense).max() > 0.1
    if dt == jnp.float32:
        np.testing.assert_allclose(swept, dense, rtol=1e-4, atol=1e-4)
    else:
        assert np.abs(swept - dense).max() <= 0.03 * np.abs(dense).max()


@pytest.mark.parametrize("body", sorted(BODIES))
def test_a_parked_row_chooses_nothing(body):
    """Rows 1 and 4 are parked and alone chose experts 1 and 3: those are
    not visited (``both`` counts), the live rows read what the dense
    dispatch gives them, a parked row reads zero."""
    p = stacks_of(BODIES[body], True)
    x, w = piece(ROWS)
    chosen = jnp.array([[0, 2], [1, 3], [2, 0], [0, 2], [3, 1], [2, 0]], jnp.int32)
    real = jnp.array([True, False, True, True, False, True])
    dense, swept = both(x, w, chosen, p, BODIES[body], real=real)
    np.testing.assert_allclose(swept[real], dense[real], rtol=1e-4, atol=1e-4)
    assert (swept[~real] == 0).all() and np.abs(dense[~real]).max() > 0


@pytest.mark.parametrize("routing,live", [("all_on_one", ROWS), ("two_empty", ROWS),
                                          ("ragged", ROWS), ("ragged", 0)])
def test_a_sweep_is_right_at_any_routing(routing, live):
    """Every row on one pair of experts (the others' blocks are never
    fetched); no row live at all (nothing is visited, the result is zero and
    finite: the call's shape does not depend on the routing)."""
    p = stacks_of(moe.GLU_SILU, True)
    x, w = piece(ROWS)
    real = jnp.arange(ROWS) < live
    dense, swept = both(x, w, ROUTINGS[routing](ROWS).astype(jnp.int32), p, moe.GLU_SILU,
                        real=real)
    np.testing.assert_allclose(swept[:live], dense[:live], rtol=1e-4, atol=1e-4)
    assert np.isfinite(swept).all() and (swept[live:] == 0).all()
    assert live == 0 or (np.abs(swept).sum(axis=1) > 0).all()


@pytest.mark.parametrize("body", sorted(BODIES))
def test_a_quarter_of_the_experts_held(body):
    """Four devices each hold a quarter of 16 routed experts: an assignment
    that lands elsewhere is dropped from the sweep and from nothing else (the
    weights are the whole model's), so the four shares add up to the uncut
    layer."""
    routed, held = 16, 4
    whole = stacks_of(BODIES[body], True, experts=routed)
    x, w = piece(ROWS)
    chosen = random_choice(ROWS, experts=routed, seed=7)
    total = 0.0
    for offset in range(0, routed, held):
        mine = jax.tree_util.tree_map(lambda a: a[:, offset:offset + held], whole)
        dense, swept = both(x, w, chosen, mine, BODIES[body], offset=offset)
        np.testing.assert_allclose(swept, dense, rtol=1e-4, atol=1e-4)
        total = total + swept
    uncut = moe._run_held(x, w, chosen, 0, whole, BODIES[body], "dequant", "off", 1)[0]
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-4)


def _leaves(kind):
    p = stacks_of(moe.GLU_SILU, kind != "plain")
    if kind == "int4":      # packed: two nibbles a byte
        p = {n: {"q": w["q"][..., ::2].astype(jnp.uint8), "s": w["s"]} for n, w in p.items()}
    if kind == "awq":       # an input scaling beside the scale
        p = {n: {**w, "a": jnp.ones(w["q"].shape[:-1])} for n, w in p.items()}
    return p


@pytest.mark.parametrize("why,rows,kernels,qm,kind,stacked,want", [
    ("a decode step over int8 leaves", ROWS, "interpret", "dequant", "int8", True, moe.SWEEP),
    ("and over plain stacks", ROWS, "interpret", "dequant", "plain", True, moe.SWEEP),
    ("w8a8 contracts in int8: correct's control", ROWS, "interpret", "w8a8", "int8", True, None),
    ("packed int4 has its own arithmetic", ROWS, "interpret", "dequant", "int4", True, None),
    ("and so has an AWQ leaf", ROWS, "interpret", "dequant", "awq", True, None),
    ("a slice without its stack would be copied", ROWS, "interpret", "dequant", "int8", False,
     None),
    ("no kernels: the CPU, or a mesh", ROWS, "off", "dequant", "int8", True, None),
    ("more rows than a sweep takes", ROWS + 1, "interpret", "dequant", "int8", True, None),
    ("up to a row tile", 8, "interpret", "dequant", "int8", True, None),
])
def test_what_a_short_piece_takes(why, rows, kernels, qm, kind, stacked, want):
    x = jax.ShapeDtypeStruct((rows, D), jnp.float32)
    assert moe.grouped_blocks(x, _leaves(kind), moe.GLU_SILU, qm, kernels, stacked) == want, why


@pytest.mark.parametrize("rows,want", [(1, "sweep"), (16, "sweep"), (32, "sweep"), (33, None),
                                       (128, None), (129, "grouped")])
def test_the_rule_at_the_kernels_own_sizes(rows, want, monkeypatch):
    """LFM2-MoE's stacks, shapes alone: a decode step of up to 32 rows
    sweeps, 33-128 rows stay dense, longer pieces are grouped."""
    monkeypatch.setattr(gm, "ROW_TILE", 128)
    monkeypatch.setattr(gm, "SWEEP_ROWS", 32)
    leaf = lambda *shape: {"q": jax.ShapeDtypeStruct(shape, jnp.int8),
                           "s": jax.ShapeDtypeStruct((*shape[:-2], shape[-1]), jnp.float32)}
    p = {"w_gate": leaf(22, 32, 2048, 1792), "w_up": leaf(22, 32, 2048, 1792),
         "w_down": leaf(22, 32, 1792, 2048)}
    x = jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16)
    how = moe.grouped_blocks(x, p, moe.GLU_SILU, "dequant", "interpret", True)
    assert {"sweep": how == moe.SWEEP, None: how is None,
            "grouped": how == ((128, 1792), (128, 2048))}[want]


@pytest.mark.parametrize("n_in,n_mid,n_out,w_dtype,n_first,rows,want", [
    (2048, 1792, 2048, "int8", 2, 16, True),      # lfm2's experts, 11 MB each
    (2560, 768, 2560, "int8", 2, 16, True),       # SmallThinker's
    (1024, 2688, 1024, "int8", 1, 32, True),      # Nemotron-H's latent experts
    (1024, 2688, 1024, "bfloat16", 1, 32, True),
    (4096, 14336, 4096, "int8", 2, 16, False),    # Mixtral's: 176 MB, no block holds one
    (2048, 1792, 2048, "float32", 2, 16, False),  # neither int8 nor the rows' dtype
    (2048, 1000, 2048, "int8", 2, 16, False),     # no whole lane tiles
    (2048, 1792, 2048, "int8", 2, 33, False),     # more rows than a sweep takes
])
def test_what_a_sweep_has_a_block_for(n_in, n_mid, n_out, w_dtype, n_first, rows, want,
                                      monkeypatch):
    monkeypatch.setattr(gm, "SWEEP_ROWS", 32)
    assert gm.expert_sweep_fits(n_in, n_mid, n_out, w_dtype, jnp.bfloat16, n_first, rows) == want


def test_the_latent_block_takes_its_stack_and_index():
    """``moe_latent_block`` handed the ``E`` blocks' layer stack and an
    index: what it gives for that layer's own leaves, dense, and its counts
    with ``visited`` the touched experts where the kernel sweeps."""
    cfg = get_config("nemotron-h-tiny", dtype="float32").scaled(     # whole lane tiles
        moe_latent_size=128, moe_intermediate_size=256)
    latent, width = cfg.moe_latent_size, cfg.moe_intermediate_size
    ks = jax.random.split(jax.random.PRNGKey(11), 8)

    def stack(key, *shape):
        return quantize_weight(jax.random.normal(key, (L, *shape)) * shape[-2] ** -0.5)

    p = {"router": jax.random.normal(ks[0], (L, cfg.d_model, cfg.n_routed_experts)),
         "router_bias": jnp.zeros((L, cfg.n_routed_experts)),
         "w_in": stack(ks[1], cfg.d_model, latent), "w_out": stack(ks[2], latent, cfg.d_model),
         "w1": stack(ks[3], cfg.experts_held, latent, width),
         "w2": stack(ks[4], cfg.experts_held, width, latent),
         "s1": stack(ks[5], cfg.d_model, cfg.moe_shared_expert_intermediate_size),
         "s2": stack(ks[6], cfg.moe_shared_expert_intermediate_size, cfg.d_model)}
    h = jax.random.normal(ks[7], (ROWS, 1, cfg.d_model), jnp.float32)
    real = (jnp.arange(ROWS) != 2)[:, None]
    own, s0 = moe.moe_latent_block(jax.tree_util.tree_map(lambda a: a[2], p), cfg, h, real)
    swept, s1 = moe.moe_latent_block(p, cfg, h, real, kernels="interpret", layer=2)
    live = np.asarray(real[:, 0])
    np.testing.assert_allclose(swept[live], own[live], rtol=1e-4, atol=1e-4)
    assert np.array_equal(s0[:3], s1[:3]) and s0[3] == cfg.experts_held and s1[3] == s1[1]


SWEPT_CELLS = {   # cell -> (roofline module, decode rows, the first stack and the result's width)
    "lfm2-8b-a1b.chat": ("conv_moe_roofline", 16, (22, 32, 2048, 1792), 2048),
    "smallthinker-21b-pp4.doc-chat": ("window_moe_roofline", 16, (12, 64, 2560, 768), 2560),
    "nemotron-3-super-ep4.decode-sat": ("ssm_moe_roofline", 32, (10, 128, 1024, 2688), 1024),
}


@pytest.mark.parametrize("meta", ["pad_add_fusion.1234", "get-tuple-element.1234", "fusion.987"])
@pytest.mark.parametrize("cell", sorted(SWEPT_CELLS))
def test_the_benchmarks_readers_find_the_sweep_by_the_name_a_trace_prints(cell, meta):
    """The call as a TPU trace names it at the three configurations' own
    shapes (result, then operands with their layouts: the scalars, the first
    stack, ...), cut as ``bench/trace_reduce.py`` keeps it: the stack's shape
    must survive the cut whatever produced the scalars, or the ``*_decode_ms``
    and ``*_expert_hbm_share`` readers lose the experts' time.
    tests/test_weight_copy_preflight.py holds the compiled programs to the
    same."""
    import importlib

    from bench import config as bench_config
    from bench import trace_reduce

    module, rows, stack, width = SWEPT_CELLS[cell]
    roofline = importlib.import_module(f"bench.{module}")
    model = dict(bench_config.load_cell(cell)["config"]["model"])
    dims = ",".join(map(str, stack))
    name = (f"%emm.21 = f32[{rows},{width}]{{1,0:T(8,128)}} custom-call("
            f"s32[{2 + stack[1]}]{{0:T(128)S(1)}} %{meta}, "
            f"s8[{dims}]{{3,2,1,0:T(8,128)(4,1)}} %get-tuple-element.4321, "
            f"s8[{dims}]{{3,2,1,0:T(8,128)(4,1)}} %get-tuple-element.4322), "
            'custom_call_target="tpu_custom_call"')
    shown = f"jit_decode/{trace_reduce.short_name(name)}"
    assert f"s8[{dims}]" in shown
    assert roofline.reads_expert_stack(shown, model)
    assert not roofline.reads_expert_stack(shown.replace(dims, "10,2688,1024"), model)
