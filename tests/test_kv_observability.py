"""KV-cache & HBM deep observability (ISSUE 8): the retained-LRU block
lifecycle, the cross-path prefix-accounting contract, the consistent
scheduler-thread gauge snapshot, the kv_cache results schema, the
headroom-model validation, and the two new monitor events.

The paged-block machinery (_paged_alloc / _paged_admit_blocks /
_paged_release) is pure host-side bookkeeping, so these tests drive it
on a bare ``Engine.__new__`` harness with hand-computed block-id
assertions — no params, no device arrays, no scheduler thread. The full
JAX engine paths are pinned by tests/test_paged_prefix.py (slow); the
end-to-end scrape rail by tests/test_bench_smoke.py.
"""

import threading
from collections import OrderedDict, deque
from types import SimpleNamespace

import numpy as np

from kserve_vllm_mini_tpu.core.schema import validate_kv_cache
from kserve_vllm_mini_tpu.monitor.events import EventDetector
from kserve_vllm_mini_tpu.profiling.headroom import (
    hbm_watermarks,
    headroom_error_pct,
)
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest

BLK = 4
POOL = 8
SLOTS = 2


def _harness(prefix_cache=True, pool=POOL):
    """A paged Engine skeleton: exactly the attributes the block
    accounting paths touch, mirroring __init__'s paged branch."""
    eng = Engine.__new__(Engine)
    eng.ecfg = EngineConfig(
        max_slots=SLOTS, max_seq_len=32, kv_layout="paged",
        kv_block_size=BLK, kv_pool_blocks=pool, prefix_cache=prefix_cache,
        min_prefill_bucket=BLK, decode_chunk=1,
    )
    eng.cfg = SimpleNamespace(
        n_layers=2, n_full_layers=2, n_kv_heads=2, head_dim=4,
        jnp_dtype=np.dtype("float32"),
    )
    eng.paged = True
    eng._blk = BLK
    eng._maxb = 32 // BLK
    eng._scratch_block = pool
    eng._free_blocks = list(range(pool))
    eng._slot_blocks = [[] for _ in range(SLOTS)]
    eng._block_table = np.full((SLOTS, eng._maxb), pool, dtype=np.int32)
    eng._table_dev = None
    eng._hash_block = {}
    eng._block_hash = {}
    eng._block_rc = {}
    eng._prefix_epoch = 0
    eng._retained_lru = OrderedDict()
    eng._block_depth = {}
    # in-transit handoff state: _kv_admin_snapshot excludes routed-not-
    # yet-consumed blocks from the fragmentation denominator
    eng._slot_handoff = [None] * SLOTS
    eng._orphan_blocks = {}
    # host-RAM KV tier (ISSUE 16): off by default in the harness
    eng._tier = OrderedDict()
    eng._tier_bytes = 0
    eng._tier_cap_bytes = 0
    eng._tier_disabled = False
    eng._tier_thrash_win = (0.0, 0)
    eng._tier_thrash_hits = 0
    eng._slot_tokens = [[] for _ in range(SLOTS)]
    eng._slot_len = [0] * SLOTS
    eng._hit_depths = deque(maxlen=4096)
    eng._obs_lock = threading.Lock()
    eng._kv_gauges = {}
    eng._running = False
    eng._thread = None
    # resilience rail (docs/RESILIENCE.md): _paged_fits consults the
    # fault registry before any plan math
    from kserve_vllm_mini_tpu.runtime.faults import FaultRegistry

    eng._faults = FaultRegistry()
    eng._kv_fault_until = 0.0
    eng.stats = {
        "prefix_hits": 0, "prefix_lookups": 0, "prefix_tokens_reused": 0,
        "kv_blocks_allocated": 0, "kv_retained_evictions": 0,
        "kv_share_reclaims": 0,
    }
    return eng


PROMPT = list(range(100, 109))  # 9 tokens -> 2 full reusable blocks


def _req(prompt=PROMPT, n=3):
    return GenRequest(prompt_tokens=list(prompt), max_new_tokens=n)


# -- retained-LRU lifecycle ---------------------------------------------------

def test_alloc_prefers_free_list_and_counts():
    eng = _harness()
    assert eng._paged_alloc() == POOL - 1  # free-list tail
    assert eng.stats["kv_blocks_allocated"] == 1
    assert eng.stats["kv_retained_evictions"] == 0


def test_eviction_order_under_pool_exhaustion():
    """_free_blocks empty -> popitem(last=False): the OLDEST retained
    block is evicted first, its content key unregistered, and the churn
    counter moves — hand-built LRU {3, 5, 1} evicts 3 then 5."""
    eng = _harness()
    eng._free_blocks = []
    for bid in (3, 5, 1):  # insertion order = recency; 3 oldest
        key = b"k%d" % bid
        eng._retained_lru[bid] = None
        eng._block_rc[bid] = 0
        eng._block_hash[bid] = key
        eng._hash_block[key] = bid
    epoch0 = eng._prefix_epoch

    assert eng._paged_alloc() == 3
    assert eng.stats["kv_retained_evictions"] == 1
    assert b"k3" not in eng._hash_block and 3 not in eng._block_hash
    assert 3 not in eng._block_rc
    assert eng._prefix_epoch == epoch0 + 1  # cached plans must expire

    assert eng._paged_alloc() == 5
    assert eng.stats["kv_retained_evictions"] == 2
    assert list(eng._retained_lru) == [1]


def test_admit_release_readmit_share_reclaim_and_balance():
    """The full lifecycle with hand-computed ids: first admission
    allocates 4 fresh blocks [7,6,5,4]; release parks the 2 registered
    prompt blocks retained (leaf-first LRU order) and frees the rest;
    the repeat prompt reclaims both via 0->1 refcount (share_reclaims,
    blocks leave the LRU) and allocates only the difference. Refcounts
    balance: after every release, free + retained == pool."""
    eng = _harness()
    r1 = _req()
    assert eng._paged_fits(r1)
    reused = eng._paged_admit_blocks(0, r1)
    assert reused == 0
    assert eng._slot_blocks[0] == [7, 6, 5, 4]  # free-list tail pops
    assert eng.stats["kv_blocks_allocated"] == 4
    assert eng.stats["prefix_lookups"] == 1
    assert eng.stats["prefix_hits"] == 0
    # prompt's 2 full blocks registered for sharing at admission
    assert set(eng._block_hash) == {7, 6}

    eng._slot_tokens[0] = list(PROMPT)
    eng._slot_len[0] = len(PROMPT)
    eng._paged_release(0)
    # leaf-first: unregistered 4,5 freed; 6 enters LRU before root 7
    assert eng._free_blocks == [0, 1, 2, 3, 4, 5]
    assert list(eng._retained_lru) == [6, 7]
    assert eng._block_rc == {6: 0, 7: 0}
    assert len(eng._free_blocks) + len(eng._retained_lru) == POOL

    r2 = _req()
    reused = eng._paged_admit_blocks(1, r2)
    assert reused == 2 * BLK  # both full blocks, exact token count
    assert eng.stats["kv_share_reclaims"] == 2  # 0->1: left the pool
    assert eng._retained_lru == OrderedDict()
    assert eng._slot_blocks[1] == [7, 6, 5, 4]  # reuse + fresh [5,4]
    assert eng._block_rc[7] == 1 and eng._block_rc[6] == 1
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_tokens_reused"] == 2 * BLK
    assert list(eng._hit_depths) == [2 * BLK]

    eng._slot_tokens[1] = list(PROMPT)
    eng._slot_len[1] = len(PROMPT)
    eng._paged_release(1)
    assert len(eng._free_blocks) + len(eng._retained_lru) == POOL


def test_double_release_is_a_noop():
    """Releasing an already-released slot must not free blocks twice,
    corrupt refcounts, or move any lifecycle counter."""
    eng = _harness()
    eng._paged_admit_blocks(0, _req())
    eng._slot_tokens[0] = list(PROMPT)
    eng._slot_len[0] = len(PROMPT)
    eng._paged_release(0)
    free, lru = list(eng._free_blocks), list(eng._retained_lru)
    rc, stats = dict(eng._block_rc), dict(eng.stats)

    eng._paged_release(0)  # double release: _slot_blocks[0] is empty
    assert eng._free_blocks == free
    assert list(eng._retained_lru) == lru
    assert eng._block_rc == rc
    assert eng.stats == stats
    assert len(eng._free_blocks) + len(eng._retained_lru) == POOL


# -- cross-path prefix accounting (engine.py:939 vs :1737) --------------------

def test_prefix_accounting_contract_matches_across_paths():
    """The block-level (_paged_admit_blocks) and slot-level
    (_pop_slot_for) reuse paths must account identically: exactly one
    prefix_lookups per admission, a prefix_hits iff reused tokens > 0,
    prefix_tokens_reused grown by the EXACT reused count, and the hit
    depth recorded. Same 9-token prompt, 8 reusable tokens each side."""
    # paged: miss then hit (8 tokens = 2 full blocks)
    paged = _harness()
    paged._paged_admit_blocks(0, _req())
    paged._slot_tokens[0] = list(PROMPT)
    paged._slot_len[0] = len(PROMPT)
    paged._paged_release(0)
    paged._paged_admit_blocks(1, _req())

    # dense: miss (no retained slots) then hit on a retained transcript
    # sharing the first 8 tokens (reuse caps at len-1 -> target is 8)
    dense = Engine.__new__(Engine)
    dense.ecfg = EngineConfig(
        max_slots=SLOTS, max_seq_len=32, prefix_cache=True,
        min_prefill_bucket=BLK,
    )
    dense.paged = False
    dense._drafter_params = None
    dense._free = [0, 1]
    dense._retained = {0: [], 1: []}
    dense._hit_depths = deque(maxlen=4096)
    dense.stats = {
        "prefix_hits": 0, "prefix_lookups": 0, "prefix_tokens_reused": 0,
    }
    slot, k = dense._pop_slot_for(list(PROMPT))
    assert k == 0
    dense._retained[slot] = list(PROMPT)  # finished request retained it
    dense._free = [1 - slot, slot]
    slot2, k2 = dense._pop_slot_for(list(PROMPT))
    assert slot2 == slot and k2 == 8

    for eng in (paged, dense):
        assert eng.stats["prefix_lookups"] == 2, eng
        assert eng.stats["prefix_hits"] == 1
        assert eng.stats["prefix_tokens_reused"] == 8
        assert list(eng._hit_depths) == [8]


# -- consistent scheduler-thread gauge snapshot -------------------------------

def test_kv_admin_snapshot_gauges_hand_computed():
    """Occupancy/fragmentation/retained-fraction from ONE _run_admin
    pass: pool 8, blocks [7,6,5,4] slot-owned with 9 live tokens,
    nothing retained -> used 4, occupancy .5, fragmentation
    1 - 9/16, logical 9*128 bytes (f32: 2*2*2*4*4 = 128 B/token)."""
    eng = _harness()
    eng._paged_admit_blocks(0, _req())
    eng._slot_tokens[0] = list(PROMPT)
    eng._slot_len[0] = len(PROMPT)
    kv = eng._kv_admin_snapshot()
    assert eng.kv_bytes_per_token() == 128
    assert kv["kv_pool_blocks"] == POOL
    assert kv["kv_free_blocks"] == 4
    assert kv["kv_retained_blocks"] == 0
    assert kv["kv_used_blocks"] == 4
    assert kv["kv_occupancy"] == 4 / 8
    assert kv["kv_retained_fraction"] == 0.0
    assert kv["kv_fragmentation"] == 1.0 - 9 / 16
    assert kv["kv_logical_bytes"] == 9 * 128
    assert kv["kv_physical_bytes"] == POOL * BLK * 128
    assert kv["kv_prefix_hit_depth_p50"] == 0  # no hits yet
    # pool arithmetic the schema validator enforces
    assert (kv["kv_free_blocks"] + kv["kv_retained_blocks"]
            + kv["kv_used_blocks"]) == kv["kv_pool_blocks"]


def test_kv_admin_snapshot_hit_depth_percentiles_and_cache_fallback():
    eng = _harness()
    eng._hit_depths.extend([4, 8, 8, 16])
    kv = eng._kv_admin_snapshot()
    assert kv["kv_prefix_hit_depth_p50"] == 8
    assert kv["kv_prefix_hit_depth_p95"] == 16
    # the cached last-consistent snapshot serves when the admin op fails
    eng._run_admin = lambda fn, timeout_s=60.0: "scheduler gone"
    eng._hit_depths.append(1000)
    again = eng._kv_admin_snapshot()
    assert again["kv_prefix_hit_depth_p95"] == 16  # stale-but-consistent


def test_kv_bytes_per_token_tracks_kv_dtype():
    eng = _harness()
    assert eng.kv_bytes_per_token() == 128  # f32: 2*2*2*4 * 4 B
    eng.ecfg = EngineConfig(
        max_slots=SLOTS, max_seq_len=32, kv_layout="paged",
        kv_block_size=BLK, kv_cache_dtype="int8",
    )
    # int8: 1 B + per-head f32 scales (4/head_dim) -> 2.0 B/elem
    assert eng.kv_bytes_per_token() == 64


# -- kv_cache schema ----------------------------------------------------------

def _good_kv_block():
    return {
        "source": "engine:snapshot", "hit_depth_p50": 8, "hit_depth_p95": 16,
        "bytes_per_token": 128, "reused_bytes": 1024, "blocks_allocated": 6,
        "retained_evictions": 2, "share_reclaims": 2, "prefix_hits": 1,
        "prefix_lookups": 2, "pool_blocks": 8, "free_blocks": 4,
        "retained_blocks": 0, "used_blocks": 4, "block_size": 4,
        "occupancy": 0.5, "retained_fraction": 0.0, "fragmentation": 0.4375,
        "logical_bytes": 1152, "physical_bytes": 4096,
        "hbm_bytes_in_use": 5e9, "hbm_peak_bytes": 6e9,
        "hbm_bytes_limit": 16e9, "headroom_estimate_bytes": 7e9,
    }


def test_validate_kv_cache_accepts_good_block():
    assert validate_kv_cache(_good_kv_block()) == []


def test_validate_kv_cache_tier_and_migration_keys():
    """ISSUE 16 optional keys: the tier/migration counters validate as
    non-negative numbers, tier_disabled is a 0/1 gauge (fraction-style
    bound), and none of them are required (pre-tier blocks stay valid)."""
    doc = _good_kv_block()
    doc.update(
        tier_demotions=3, tier_promotions=2, tier_hits=1, tier_blocks=2,
        tier_bytes=1024, tier_capacity_bytes=4096, tier_disabled=0,
        migrated_blocks=5, migrated_bytes=2560, export_blocks=5,
    )
    assert validate_kv_cache(doc) == []
    for mutate, fragment in [
        (lambda d: d.update(tier_demotions=-1), "tier_demotions"),
        (lambda d: d.update(tier_disabled=2), "tier_disabled above 1"),
        (lambda d: d.update(migrated_bytes="x"), "migrated_bytes"),
    ]:
        bad = _good_kv_block()
        mutate(bad)
        errs = validate_kv_cache(bad)
        assert any(fragment in e for e in errs), (fragment, errs)


def test_validate_kv_cache_rejects_violations():
    assert validate_kv_cache(None) == ["kv_cache block is not an object"]
    for mutate, fragment in [
        (lambda d: d.pop("hit_depth_p50"), "hit_depth_p50"),
        (lambda d: d.update(retained_evictions=-1), "retained_evictions"),
        (lambda d: d.update(occupancy=1.5), "occupancy above 1"),
        (lambda d: d.update(hit_depth_p95=2), "hit_depth_p95 < hit_depth_p50"),
        (lambda d: d.update(free_blocks=5), "pool arithmetic"),
        (lambda d: d.update(source=7), "source is not a string"),
    ]:
        doc = _good_kv_block()
        mutate(doc)
        errs = validate_kv_cache(doc)
        assert any(fragment in e for e in errs), (fragment, errs)


# -- headroom-model validation ------------------------------------------------

def test_headroom_error_pct_sign_and_absence():
    assert headroom_error_pct(None, 5e9) is None
    assert headroom_error_pct(5e9, None) is None
    assert headroom_error_pct(0, 5e9) is None
    assert headroom_error_pct("x", 5e9) is None
    # overestimate -> positive (wasteful); underestimate -> negative (OOM)
    assert headroom_error_pct(12e9, 10e9) == 20.0
    assert headroom_error_pct(8e9, 10e9) == -20.0


def test_hbm_watermarks_graceful_absence_and_passthrough():
    class Dev:
        def __init__(self, stats):
            self._s = stats

        def memory_stats(self):
            return self._s

    full = hbm_watermarks(Dev({"bytes_in_use": 5, "peak_bytes_in_use": 7,
                               "bytes_limit": 16}))
    assert full == {"bytes_in_use": 5, "peak_bytes_in_use": 7,
                    "bytes_limit": 16}
    # no fabricated zeros: CPU devices report nothing
    assert hbm_watermarks(Dev(None)) == {}
    assert hbm_watermarks(Dev({"largest_free_block": 3})) == {}
    # zero-valued peak/limit are dropped, in_use survives
    assert hbm_watermarks(Dev({"bytes_in_use": 5, "bytes_limit": 0})) == {
        "bytes_in_use": 5
    }


def test_telemetry_kv_cache_block_degradation_and_headroom_join():
    from kserve_vllm_mini_tpu.analysis import telemetry

    assert telemetry.kv_cache_block(None) == {}
    # runtime without the rail (external engine): no block
    assert telemetry.kv_cache_block(
        "http://x", runtime_metrics={"kvmini_tpu_queue_depth": 1.0}
    ) == {}
    # rail exported but zero activity, no pool, no HBM: no block
    zeros = {m: 0.0 for m in telemetry.KV_METRIC_KEYS.values()
             if not m.endswith(("_pool_blocks", "_free_blocks",
                                "_retained_blocks", "_used_blocks",
                                "_block_size", "_occupancy",
                                "_retained_fraction", "_fragmentation",
                                "_logical_bytes", "_physical_bytes"))
             and "hbm_bytes" not in m and "hbm_peak" not in m}
    assert telemetry.kv_cache_block("http://x", runtime_metrics=zeros) == {}
    # live run: block lands, and estimate+peak close headroom_error_pct
    live = dict(zeros)
    live.update({
        "kvmini_tpu_cache_lookups_total": 2.0,
        "kvmini_tpu_prefix_hits_total": 1.0,
        "kvmini_tpu_kv_prefix_hit_depth_p50": 8.0,
        "kvmini_tpu_kv_prefix_hit_depth_p95": 16.0,
        "kvmini_tpu_hbm_peak_bytes": 10e9,
        "kvmini_tpu_hbm_headroom_estimate_bytes": 12e9,
    })
    out = telemetry.kv_cache_block("http://x", runtime_metrics=live)
    assert out["kv_cache"]["hit_depth_p95"] == 16.0
    assert out["kv_cache"]["source"] == "metrics:scrape"
    assert out["headroom_error_pct"] == 20.0


# -- monitor events -----------------------------------------------------------

def _sample(t, runtime=None):
    s = {"t": float(t)}
    if runtime is not None:
        s["runtime"] = runtime
    return s


def test_kv_thrash_fires_on_sustained_eviction_rate():
    """Rate-based (delta/dt), not level-based: a ramp of 8 evictions/s
    for 3 consecutive sample pairs fires; a large static total never
    does (history is not live thrash)."""
    det = EventDetector(kv_thrash_rate=4.0, kv_thrash_samples=3)
    fired = []
    for i, total in enumerate([0.0, 8.0, 16.0, 24.0, 32.0]):
        fired += det.observe(_sample(
            i, runtime={"kv_retained_evictions_total": total}
        ))
    assert [e.type for e in fired] == ["kv_thrash"]
    assert fired[0].t == 3.0  # pairs (0,1),(1,2),(2,3) -> third crossing
    assert fired[0].data["evictions_per_s"] == 8.0

    # frozen large total: no rate, no event
    det2 = EventDetector(kv_thrash_rate=4.0, kv_thrash_samples=3)
    fired2 = []
    for i in range(6):
        fired2 += det2.observe(_sample(
            i, runtime={"kv_retained_evictions_total": 1e6}
        ))
    assert fired2 == []


def test_kv_thrash_resets_on_quiet_sample():
    det = EventDetector(kv_thrash_rate=4.0, kv_thrash_samples=3)
    fired = []
    #      burst     quiet    burst burst  (run resets at the quiet pair)
    for i, total in enumerate([0.0, 8.0, 8.0, 16.0, 24.0]):
        fired += det.observe(_sample(
            i, runtime={"kv_retained_evictions_total": total}
        ))
    assert fired == []


def test_hbm_watermark_high_level_triggered():
    """Level-based and immediate: one sample at >= 92% of the limit
    fires; below stays quiet; absent limit can never divide-by-zero."""
    det = EventDetector(hbm_high_fraction=0.92)
    quiet = det.observe(_sample(
        0, runtime={"hbm_bytes_in_use": 10e9, "hbm_bytes_limit": 16e9}
    ))
    assert quiet == []
    fired = det.observe(_sample(
        1, runtime={"hbm_bytes_in_use": 15e9, "hbm_bytes_limit": 16e9}
    ))
    assert [e.type for e in fired] == ["hbm_watermark_high"]
    assert fired[0].data["fraction"] == 15e9 / 16e9

    det2 = EventDetector()
    assert det2.observe(_sample(
        0, runtime={"hbm_bytes_in_use": 15e9}  # no limit reported
    )) == []
    assert det2.observe(_sample(
        1, runtime={"hbm_bytes_in_use": 15e9, "hbm_bytes_limit": 0.0}
    )) == []


# -- in-transit handoff blocks vs fragmentation (ISSUE 16, satellite) ---------

def test_fragmentation_excludes_in_transit_handoff_blocks():
    """A routed-not-yet-consumed v2 slot owns blocks with ZERO live
    tokens (the lane is still writing them). Counting them in the
    fragmentation denominator would read the handoff window as waste:
    hand-computed, slot 0 settled with 9 live tokens over 4 blocks and
    slot 1 in transit with 2 blocks -> fragmentation stays 1 - 9/16,
    not 1 - 9/24. Occupancy still counts ALL used blocks honestly."""
    eng = _harness()
    eng._paged_admit_blocks(0, _req())
    eng._slot_tokens[0] = list(PROMPT)
    eng._slot_len[0] = len(PROMPT)
    # slot 1: routed to the lane — blocks allocated, handoff pending
    eng._slot_blocks[1] = [eng._paged_alloc(), eng._paged_alloc()]
    for bid in eng._slot_blocks[1]:
        eng._block_rc[bid] = 1
    eng._slot_handoff[1] = {"handle": object(), "t_route": 0.0}
    kv = eng._kv_admin_snapshot()
    assert kv["kv_used_blocks"] == 6
    assert kv["kv_occupancy"] == 6 / 8
    assert kv["kv_fragmentation"] == 1.0 - 9 / 16  # settled blocks only
    # consume lands: the same blocks now count (still 0 live tokens
    # until activation, but they are no longer in transit)
    eng._slot_handoff[1] = None
    kv2 = eng._kv_admin_snapshot()
    assert kv2["kv_fragmentation"] == 1.0 - 9 / 24


# -- host-RAM KV tier (ISSUE 16) ----------------------------------------------

def _tier_harness(cap=4096):
    """_harness plus an armed tier with stubbed device I/O: demotion
    'reads' a block as a tagged dict, promotion records its uploads."""
    eng = _harness()
    eng._tier_cap_bytes = cap
    eng.stats.update({"kv_tier_demotions": 0, "kv_tier_promotions": 0,
                      "kv_tier_hits": 0})
    writes = []
    eng._tier_block_bytes = lambda: 128
    eng._read_block_host = lambda bid: {"from_bid": bid}
    eng._write_block_dev = lambda bid, leaves: writes.append((bid, leaves))
    return eng, writes


def test_tier_demote_on_eviction_promote_on_readmission():
    """The tier round trip with hand-computed ids: retained blocks
    evicted under pool pressure land in the tier (content-keyed, byte
    accounting exact), and a re-admission of the same prompt promotes
    the contiguous chain back into its fresh blocks — reuse depth
    identical to a device-resident hit, one hit counted."""
    eng, writes = _tier_harness()
    assert eng._paged_admit_blocks(0, _req()) == 0
    eng._slot_tokens[0] = list(PROMPT)
    eng._slot_len[0] = len(PROMPT)
    eng._paged_release(0)  # 2 prompt blocks retained: LRU [6 (leaf), 7]
    eng._free_blocks = []
    eng._paged_alloc()  # evicts 6 -> demoted
    eng._paged_alloc()  # evicts 7 -> demoted
    assert eng.stats["kv_retained_evictions"] == 2
    assert eng.stats["kv_tier_demotions"] == 2
    assert len(eng._tier) == 2 and eng._tier_bytes == 256
    assert [e["kv"]["from_bid"] for e in eng._tier.values()] == [6, 7]
    # re-admission: no device-resident prefix left, but the tier holds
    # the whole chain — promotion uploads root-first into fresh blocks
    eng._free_blocks = [0, 1, 2, 3]
    off = eng._paged_admit_blocks(1, _req())
    assert off == 2 * BLK  # same reuse depth a device hit would give
    assert eng.stats["kv_tier_promotions"] == 2
    assert eng.stats["kv_tier_hits"] == 1
    assert eng.stats["prefix_tokens_reused"] == 2 * BLK
    # root (depth 1, was block 7) lands in the chain's first fresh block
    assert [w[1]["from_bid"] for w in writes] == [7, 6]
    assert len(eng._tier) == 0 and eng._tier_bytes == 0  # entries moved


def test_tier_capacity_bound_evicts_oldest_demotion():
    """A tier at capacity makes room oldest-first, and a tier smaller
    than one block stays empty instead of thrashing on every eviction."""
    eng, _ = _tier_harness(cap=256)  # exactly 2 stub blocks
    for i, key in enumerate((b"a", b"b", b"c")):
        eng._tier_demote(i, key, i + 1)
    assert list(eng._tier) == [b"b", b"c"]  # b"a" was the oldest
    assert eng._tier_bytes == 256
    tiny, _ = _tier_harness(cap=64)  # under one block
    tiny._tier_demote(0, b"x", 1)
    assert len(tiny._tier) == 0 and tiny._tier_bytes == 0


def test_tier_thrash_guard_disables_sticky_and_clears():
    """Sustained eviction churn at the monitor's kv_thrash thresholds
    (>= 4/s over 3 consecutive windows) disables the tier for the rest
    of the run: entries drop, the gauge flips, demotion and promotion
    both refuse — moving thrash onto PCIe is worse than none."""
    import time as time_mod

    eng, _ = _tier_harness()
    eng._tier_demote(5, b"seed", 1)
    assert len(eng._tier) == 1
    for _ in range(3):
        _, ev0 = eng._tier_thrash_win
        eng._tier_thrash_win = (time_mod.time() - 1.1, ev0)
        eng.stats["kv_retained_evictions"] = ev0 + 11  # ~10/s >> 4/s
        eng._tier_thrash_tick()
    assert eng._tier_disabled
    assert len(eng._tier) == 0 and eng._tier_bytes == 0
    # sticky: the eviction path stops demoting from here on
    eng._free_blocks = []
    eng._retained_lru[6] = None
    eng._block_rc[6] = 0
    eng._block_hash[6] = b"late"
    eng._hash_block[b"late"] = 6
    demos = eng.stats["kv_tier_demotions"]
    assert eng._paged_alloc() == 6  # evicted outright, not demoted
    assert eng.stats["kv_tier_demotions"] == demos
    assert len(eng._tier) == 0
    epoch = eng._prefix_epoch
    eng._tier_thrash_tick()  # no-op once disabled
    assert eng._prefix_epoch == epoch
    kv = eng._kv_admin_snapshot()
    assert kv["kv_tier_disabled"] == 1
    assert kv["kv_tier_blocks"] == 0 and kv["kv_tier_bytes"] == 0


def test_tier_quiet_churn_never_disables():
    import time as time_mod

    eng, _ = _tier_harness()
    for _ in range(5):
        _, ev0 = eng._tier_thrash_win
        eng._tier_thrash_win = (time_mod.time() - 1.1, ev0)
        eng.stats["kv_retained_evictions"] = ev0 + 2  # ~2/s < 4/s
        eng._tier_thrash_tick()
    assert not eng._tier_disabled


def test_host_tier_pricing_never_touches_hbm_estimate():
    """profiling/headroom.py companion math: one demoted block of the
    harness config costs 2*L*KVH*BLK*D*4 = 512 host bytes (the same
    kv_elem_bytes price as HBM, applied to host RAM), the capacity
    helper floors, and estimate_serving_bytes has NO tier parameter at
    all — the tier can never inflate the HBM admission estimate."""
    import inspect

    from kserve_vllm_mini_tpu.profiling.headroom import (
        estimate_serving_bytes,
        host_tier_block_bytes,
        host_tier_capacity_blocks,
    )

    cfg = SimpleNamespace(n_layers=2, n_kv_heads=2, head_dim=4,
                          jnp_dtype=np.dtype("float32"))
    assert host_tier_block_bytes(cfg, BLK) == 512
    assert host_tier_capacity_blocks(4096, cfg, BLK) == 8
    assert host_tier_capacity_blocks(511, cfg, BLK) == 0
    assert host_tier_capacity_blocks(None, cfg, BLK) == 0
    params = inspect.signature(estimate_serving_bytes).parameters
    assert not any("tier" in name for name in params)
