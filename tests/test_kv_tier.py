"""Tiered KV memory (ISSUE 10): host-RAM/disk spillover for the prefix
cache with restore on match (docs/SERVING.md "KV tiering").

Invariants under test: spill/restore byte round-trips exactly (fp32,
bf16, int8 + scale planes), referenced blocks are never spilled, restored
blocks re-enter the index under their original ``(parent_hash, tokens)``
key, LRU ordering inside the tier (host demotes/drops oldest first, disk
drops oldest first), disk corruption reads back as a miss (re-prefill —
never a crash), restores compose with cancel/deadline/replica-death, and
the disabled path is byte-for-byte the tier-less stack."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kv_tier import TieredKVStore
from deepspeed_tpu.inference.v2.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.inference.v2.testing import greedy_generate
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.serving.config import KVTierConfig

VOCAB = 128
BS = 8          # kv block size used throughout


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(vocab_size=VOCAB, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=2,
                            max_seq_len=128, norm="rmsnorm",
                            activation="silu", position="rope")
    model = CausalLM(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def make_engine(model, params, tier=True, kv_blocks=14, quant=False,
                qdtype="int8", dtype=None, host_bytes=64 << 20,
                disk_path=None, disk_bytes=0, prefix=True, max_seqs=4):
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=max_seqs,
        max_chunk_tokens=32, kv_blocks=kv_blocks, kv_block_size=BS,
        max_tracked_sequences=64, enable_prefix_cache=prefix,
        kv_quant_enabled=quant, kv_quant_dtype=qdtype)
    eng = InferenceEngineV2(model, params=params, config=vcfg)
    if tier:
        eng.configure_kv_tier(True, host_bytes=host_bytes,
                              disk_path=disk_path, disk_bytes=disk_bytes)
    return eng


def rand_prompt(rng, n):
    return rng.integers(0, VOCAB, size=n).tolist()


def shared_prefix_reqs(rng, k_prompts=4, n_req=12, sys_len=32, tail=6):
    sys_prompts = [rand_prompt(rng, sys_len) for _ in range(k_prompts)]
    return [sys_prompts[i % k_prompts] + rand_prompt(rng, tail)
            for i in range(n_req)]


def block_slabs(eng, block):
    """One block's slab content from every pool tensor, materialized."""
    return {name: np.asarray(pool[:, block])
            for name, pool in eng.state_manager.kv_cache.items()}


# ------------------------------------------------------- store unit tests
def entry(rng, nbytes=1024, dtype=np.float32):
    n = nbytes // np.dtype(dtype).itemsize
    return {"k": rng.normal(size=n).astype(dtype),
            "v": rng.normal(size=n).astype(dtype)}


def test_store_put_get_roundtrip_and_pop():
    rng = np.random.default_rng(0)
    st = TieredKVStore(host_max_bytes=1 << 20)
    e = entry(rng)
    assert st.put(("h", (1, 2)), e)
    got = st.get(("h", (1, 2)))
    np.testing.assert_array_equal(got["k"], e["k"])
    np.testing.assert_array_equal(got["v"], e["v"])
    # get pops: the device pool is the authority again
    assert st.get(("h", (1, 2))) is None
    assert st.stats["hits"] == 1 and st.stats["misses"] == 1
    assert st.host_bytes == 0


def test_store_host_lru_drops_oldest_without_disk():
    rng = np.random.default_rng(1)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    st = TieredKVStore(host_max_bytes=2 * nbytes)
    for i in range(3):
        assert st.put((i,), entry(rng))
    host, disk = st.lru_keys()
    assert host == [(1,), (2,)] and disk == []   # oldest dropped first
    assert st.stats["dropped"] == 1
    assert st.get((0,)) is None


def test_store_lru_touch_on_overwrite():
    rng = np.random.default_rng(2)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    st = TieredKVStore(host_max_bytes=2 * nbytes)
    st.put((0,), entry(rng))
    st.put((1,), entry(rng))
    st.put((0,), entry(rng))            # overwrite refreshes recency
    st.put((2,), entry(rng))            # now (1,) is the LRU victim
    host, _ = st.lru_keys()
    assert host == [(0,), (2,)]


def test_store_demotes_to_disk_and_restores(tmp_path):
    rng = np.random.default_rng(3)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    st = TieredKVStore(host_max_bytes=nbytes,
                       disk_path=str(tmp_path), disk_max_bytes=10 * nbytes)
    e0, e1 = entry(rng), entry(rng)
    st.put((0,), e0)
    st.put((1,), e1)                    # (0,) demotes to disk
    host, disk = st.lru_keys()
    assert host == [(1,)] and disk == [(0,)]
    assert st.stats["demoted"] == 1 and st.disk_bytes > 0
    got = st.get((0,))                  # disk round-trip, CRC-checked
    np.testing.assert_array_equal(got["k"], e0["k"])
    np.testing.assert_array_equal(got["v"], e0["v"])
    assert st.disk_bytes == 0


def test_store_disk_lru_bound_drops_oldest(tmp_path):
    rng = np.random.default_rng(4)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    st = TieredKVStore(host_max_bytes=nbytes,
                       disk_path=str(tmp_path), disk_max_bytes=2 * nbytes)
    for i in range(4):                  # 1 host + 2 disk fit; 1 drops
        st.put((i,), entry(rng))
    host, disk = st.lru_keys()
    assert host == [(3,)] and disk == [(1,), (2,)]
    assert st.stats["dropped"] == 1
    assert st.get((0,)) is None


def test_store_failed_demotion_leaves_no_partial_file(tmp_path):
    """A demotion whose disk write fails must remove any partial file —
    it sits outside disk_bytes accounting and the live process's sweep
    never touches it (an intermittent-I/O server would leak forever)."""
    rng = np.random.default_rng(65)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    st = TieredKVStore(host_max_bytes=nbytes,
                       disk_path=str(tmp_path), disk_max_bytes=1 << 20)

    def boom(key, arr):
        with open(os.path.join(tmp_path, f"{key}.swp"), "wb") as fh:
            fh.write(b"partial")            # torn write, then failure
        raise IOError("ENOSPC")

    st._swapper.swap_out = boom
    st.put((0,), entry(rng))
    st.put((1,), entry(rng))                # (0,) demotes -> write fails
    assert st.stats["dropped"] == 1
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".swp")]
    st.close()


def test_restore_lookahead_capped_at_pool_budget(model_and_params):
    """A spilled chain longer than the pool's free+evictable capacity
    must not be popped (and disk-churned) past what can actually be
    restored — the lookahead caps BEFORE touching the tier."""
    model, params = model_and_params
    rng = np.random.default_rng(66)
    eng = make_engine(model, params, kv_blocks=8)
    sm = eng.state_manager
    prompt = rand_prompt(rng, 6 * BS + 2)   # 6 full blocks
    eng.put([1], [prompt[:4 * BS]])
    eng.put([1], [prompt[4 * BS:]])
    eng.flush(1)
    assert sm._evict(6) == 6                # all 6 spilled
    # occupy the pool so only 2 blocks can come back
    p_b = rand_prompt(rng, 5 * BS)
    eng.put([2], [p_b[:32]])
    eng.put([2], [p_b[32:]])
    free = sm.allocator.free_blocks
    assert free < 6
    hits0 = eng.tier_stats()["hits"]
    matched = eng.match_prefix(3, prompt)
    assert matched == free * BS             # restored what fit
    # only the restorable prefix was popped: no pop-then-readmit churn
    assert eng.tier_stats()["hits"] - hits0 == free
    assert len(sm._tier) == 6 - free        # tail untouched in the tier
    eng.flush(2)
    eng.flush(3)


def test_store_disk_corruption_is_miss(tmp_path):
    rng = np.random.default_rng(5)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    st = TieredKVStore(host_max_bytes=nbytes,
                       disk_path=str(tmp_path), disk_max_bytes=10 * nbytes)
    st.put((0,), entry(rng))
    st.put((1,), entry(rng))            # (0,) on disk now
    swp = [f for f in os.listdir(tmp_path) if f.endswith(".swp")]
    assert swp
    with open(os.path.join(tmp_path, swp[0]), "r+b") as fh:
        fh.seek(10)
        fh.write(b"\xff" * 32)          # same size, wrong bytes -> CRC
    assert st.get((0,)) is None         # miss, not a crash
    assert st.stats["corrupt"] == 1


def test_store_disk_truncation_is_miss(tmp_path):
    rng = np.random.default_rng(6)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    st = TieredKVStore(host_max_bytes=nbytes,
                       disk_path=str(tmp_path), disk_max_bytes=10 * nbytes)
    st.put((0,), entry(rng))
    st.put((1,), entry(rng))
    swp = [f for f in os.listdir(tmp_path) if f.endswith(".swp")]
    path = os.path.join(tmp_path, swp[0])
    with open(path, "r+b") as fh:       # torn write: half the file
        fh.truncate(os.path.getsize(path) // 2)
    assert st.get((0,)) is None
    assert st.stats["corrupt"] == 1


def test_store_disk_files_namespaced_per_store(tmp_path):
    """Replicas share one disk_path (the frontend applies a single
    config fleet-wide): two stores must never overwrite or delete each
    other's spill files."""
    rng = np.random.default_rng(60)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    a = TieredKVStore(host_max_bytes=nbytes, disk_path=str(tmp_path),
                      disk_max_bytes=10 * nbytes)
    b = TieredKVStore(host_max_bytes=nbytes, disk_path=str(tmp_path),
                      disk_max_bytes=10 * nbytes)
    ea, eb = entry(rng), entry(rng)
    a.put((0,), ea)
    a.put((1,), entry(rng))             # a's (0,) demotes to disk
    b.put((0,), eb)
    b.put((1,), entry(rng))             # b's (0,) demotes to disk
    got_a, got_b = a.get((0,)), b.get((0,))
    assert got_a is not None and got_b is not None
    np.testing.assert_array_equal(got_a["k"], ea["k"])
    np.testing.assert_array_equal(got_b["k"], eb["k"])
    assert a.stats["corrupt"] == 0 and b.stats["corrupt"] == 0


def test_store_counters_stay_monotonic_through_readmit():
    """The published spill counter must never dip — a transient
    decrement would read as an engine swap to the frontend's
    counter-reset heuristic. readmit re-inserts WITHOUT counting."""
    rng = np.random.default_rng(61)
    st = TieredKVStore(host_max_bytes=1 << 20)
    st.put((0,), entry(rng))
    assert st.stats == {**st.stats, "spilled": 1}
    got = st.get((0,))
    st.readmit((0,), got)
    assert st.stats["spilled"] == 1     # unchanged, not 2-then-1
    assert st.stats["hits"] == 0 and st.stats["misses"] == 1
    assert (0,) in st                   # entry really is back


def test_store_sweeps_dead_owner_files_keeps_live_ones(tmp_path):
    """A shared disk_path must not grow without bound across process
    restarts: construction removes spill files whose owning pid is
    dead, and leaves this process's (and undecidable) files alone."""
    rng = np.random.default_rng(62)
    stale = os.path.join(tmp_path, "kvtier_999999999_0_0.swp")
    with open(stale, "wb") as fh:
        fh.write(b"x" * 64)
    mine = os.path.join(tmp_path, f"kvtier_{os.getpid()}_77_0.swp")
    with open(mine, "wb") as fh:
        fh.write(b"y" * 64)
    other = os.path.join(tmp_path, "unrelated.swp")
    with open(other, "wb") as fh:
        fh.write(b"z" * 64)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    st = TieredKVStore(host_max_bytes=nbytes, disk_path=str(tmp_path),
                       disk_max_bytes=10 * nbytes)
    assert not os.path.exists(stale)        # dead owner: swept
    assert os.path.exists(mine)             # this process: kept
    assert os.path.exists(other)            # not ours to judge: kept
    st.close()


def test_store_close_removes_own_disk_files(tmp_path):
    """A replaced engine's store (supervisor restart) must not orphan
    its spill files until process exit — close() cleans them up."""
    rng = np.random.default_rng(63)
    one = entry(rng)
    nbytes = sum(a.nbytes for a in one.values())
    st = TieredKVStore(host_max_bytes=nbytes, disk_path=str(tmp_path),
                       disk_max_bytes=10 * nbytes)
    st.put((0,), entry(rng))
    st.put((1,), entry(rng))                # (0,) demoted to disk
    assert [f for f in os.listdir(tmp_path) if f.endswith(".swp")]
    st.close()
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".swp")]


def test_store_disk_only_configuration_demotes_directly(tmp_path):
    """An entry too big for the host bound goes STRAIGHT to the disk
    tier when one exists (disk-heavy configs must not be silently
    dead); without a disk tier it drops as before."""
    rng = np.random.default_rng(64)
    e = entry(rng)
    st = TieredKVStore(host_max_bytes=16,       # smaller than any entry
                       disk_path=str(tmp_path), disk_max_bytes=1 << 20)
    assert st.put((0,), e)
    assert st.occupancy()["host_blocks"] == 0
    assert st.occupancy()["disk_blocks"] == 1
    assert st.stats["spilled"] == 1 and st.stats["dropped"] == 0
    got = st.get((0,))
    np.testing.assert_array_equal(got["k"], e["k"])
    st.close()
    no_disk = TieredKVStore(host_max_bytes=16)
    assert not no_disk.put((0,), entry(rng))
    assert no_disk.stats["dropped"] == 1


def test_tier_pressure_baseline_survives_transient_stats_failure():
    """A replica whose tier_stats() read fails during an emitting tick
    must keep its baseline — wholesale replacement would re-emit its
    lifetime totals as a phantom burst when it recovers."""
    from types import SimpleNamespace

    from deepspeed_tpu.serving.frontend import ServingFrontend
    from deepspeed_tpu.telemetry.journal import OpsJournal

    class Eng:
        def __init__(self):
            self.s = {"spilled": 0, "restored": 0, "dropped": 0,
                      "host_bytes": 0}
            self.fail = False

        def tier_stats(self):
            if self.fail:
                raise RuntimeError("transient")
            return dict(self.s)

    e1, e2 = Eng(), Eng()
    fe = SimpleNamespace(
        router=SimpleNamespace(replicas=[
            SimpleNamespace(replica_id=0, engine=e1),
            SimpleNamespace(replica_id=1, engine=e2)]),
        journal=OpsJournal(source="serving"),
        _tier_last={}, _tier_journal_t=-10.0)
    tick = ServingFrontend._maybe_journal_tier_pressure
    e1.s["spilled"] = 10
    tick(fe)
    fe._tier_journal_t = -10.0
    assert fe.journal.count("kv_tier_pressure") == 1
    e1.fail = True                  # transient read failure on e1...
    e2.s["spilled"] = 5             # ...while e2's churn emits
    tick(fe)
    fe._tier_journal_t = -10.0
    assert fe.journal.count("kv_tier_pressure") == 2
    e1.fail = False                 # e1 recovers, counters unchanged
    tick(fe)
    evs = fe.journal.events(kinds=("kv_tier_pressure",))
    assert len(evs) == 2            # no phantom re-emit of e1's 10
    assert evs[0]["detail"]["spilled"] == 10
    assert evs[1]["detail"]["spilled"] == 5


# --------------------------------------------------- spill/restore invariants
@pytest.mark.parametrize("quant,qdtype",
                         [(False, "int8"), (True, "int8"),
                          (True, "fp8_e4m3")],
                         ids=["fp", "int8", "fp8"])
def test_spill_restore_byte_roundtrip(model_and_params, quant, qdtype):
    """An evicted block's slabs (int8/fp8 + scale planes under kv_quant)
    must come back bit-identical when the prefix is matched again — the
    ISSUE 13 dtype axis rides the same test, not a copy."""
    model, params = model_and_params
    rng = np.random.default_rng(7)
    eng = make_engine(model, params, quant=quant, qdtype=qdtype,
                      kv_blocks=16)
    prompt = rand_prompt(rng, 3 * BS + 2)
    eng.put([1], [prompt])
    sm = eng.state_manager
    seq = sm.get_sequence(1)
    indexed = list(seq.kv_blocks[:3])   # 3 full indexed blocks
    before = {b: block_slabs(eng, b) for b in indexed}
    keys = [sm._block_hash[b] for b in indexed]
    eng.flush(1)
    assert sm._evict(3) == 3            # spill all three
    t = eng.tier_stats()
    assert t["spilled"] == 3 and t["host_blocks"] == 3
    matched = eng.match_prefix(2, prompt)
    assert matched == 3 * BS
    assert eng.tier_stats()["restored"] == 3
    seq2 = sm.get_sequence(2)
    for i, b_new in enumerate(seq2.kv_blocks):
        after = block_slabs(eng, b_new)
        for name in after:
            np.testing.assert_array_equal(
                after[name], before[indexed[i]][name]), name
        # restored under the ORIGINAL (parent_hash, tokens) key
        assert sm._block_hash[b_new] == keys[i]
        assert sm._index[keys[i]] == b_new


def test_bf16_roundtrip_parity():
    """bf16 pools spill/restore exactly (np round-trips ml_dtypes)."""
    cfg = TransformerConfig(vocab_size=VOCAB, hidden_size=64,
                            intermediate_size=128, num_layers=2,
                            num_heads=2, max_seq_len=128, norm="rmsnorm",
                            activation="silu", position="rope",
                            dtype=jnp.bfloat16)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(8)
    reqs = shared_prefix_reqs(rng)
    g_off = greedy_generate(make_engine(model, params, tier=False), reqs,
                            uid_base=100, max_new_tokens=4)
    eng = make_engine(model, params, tier=True)
    g_on = greedy_generate(eng, reqs, uid_base=100, max_new_tokens=4)
    assert eng.tier_stats()["restored"] > 0
    assert g_on == g_off


def test_referenced_block_never_spilled(model_and_params):
    """A block still shared by a live sequence is not evictable, so it
    can never reach the tier — eviction (and spill) touch only blocks
    whose sole reference is the cache's own."""
    model, params = model_and_params
    rng = np.random.default_rng(9)
    eng = make_engine(model, params, kv_blocks=16)
    prompt = rand_prompt(rng, 2 * BS + 1)
    eng.put([1], [prompt])              # seq 1 holds its blocks
    sm = eng.state_manager
    held = list(sm.get_sequence(1).kv_blocks[:2])
    assert sm._evict(10) == 0           # everything referenced: no-op
    assert eng.tier_stats()["spilled"] == 0
    for b in held:
        assert b in sm._block_hash      # still indexed, still resident


def test_partial_block_never_spilled(model_and_params):
    """The last, partially-filled block is never indexed, so eviction
    (and therefore the tier) can never see it."""
    model, params = model_and_params
    rng = np.random.default_rng(10)
    eng = make_engine(model, params, kv_blocks=16)
    prompt = rand_prompt(rng, BS + 3)   # 1 full + 1 partial block
    eng.put([1], [prompt])
    eng.flush(1)
    sm = eng.state_manager
    assert sm._evict(10) == 1           # only the full block was indexed
    assert eng.tier_stats()["spilled"] == 1


def test_restore_shares_lru_with_device_hits(model_and_params):
    """After a restore the block behaves exactly like a device-indexed
    block: a second match of the same prefix hits the index without
    touching the tier."""
    model, params = model_and_params
    rng = np.random.default_rng(11)
    eng = make_engine(model, params, kv_blocks=16)
    prompt = rand_prompt(rng, 2 * BS + 2)
    eng.put([1], [prompt])
    eng.flush(1)
    sm = eng.state_manager
    sm._evict(2)
    assert eng.match_prefix(2, prompt) == 2 * BS
    hits0 = eng.tier_stats()["hits"]
    assert eng.match_prefix(3, prompt) == 2 * BS    # pure device hits
    assert eng.tier_stats()["hits"] == hits0
    eng.flush(2)
    eng.flush(3)


def test_restore_under_full_pool_evicts_or_degrades(model_and_params):
    """A tier hit with zero free blocks evicts a colder cache resident
    to make room; when nothing is evictable the match degrades to a
    re-prefill (miss) instead of raising."""
    model, params = model_and_params
    rng = np.random.default_rng(12)
    eng = make_engine(model, params, kv_blocks=6, max_seqs=4)
    sm = eng.state_manager
    p_a = rand_prompt(rng, 2 * BS + 2)  # 2 full blocks + a match tail
    eng.put([1], [p_a])
    eng.flush(1)
    sm._evict(2)                        # A spilled to the tier
    # fill the pool with a live (referenced) sequence: nothing evictable
    p_b = rand_prompt(rng, 5 * BS + 3)
    eng.put([2], [p_b[:32]])
    eng.put([2], [p_b[32:]])
    assert sm.allocator.free_blocks == 0
    assert sm.evictable_blocks == 0
    # restore impossible: the walk degrades to a miss, no exception
    assert eng.match_prefix(3, p_a) == 0
    assert eng.tier_stats()["restored"] == 0
    # counters describe the degrade honestly: the failed restore is a
    # MISS (not a hit) and the readmit is not a new spill
    assert eng.tier_stats()["hits"] == 0
    assert eng.tier_stats()["misses"] >= 1
    assert eng.tier_stats()["spilled"] == 2
    # the entry survived for a calmer moment
    assert sm._tier is not None and len(sm._tier) >= 1
    eng.flush(2)
    eng.flush(3)
    # with the pool free again the same match restores
    assert eng.match_prefix(4, p_a) == 2 * BS
    assert eng.tier_stats()["restored"] == 2


def test_disk_corrupt_entry_reprefills_end_to_end(model_and_params,
                                                  tmp_path):
    """Corrupting the on-disk spill file must degrade that prefix to a
    re-prefill — generations still complete, streams still match."""
    model, params = model_and_params
    rng = np.random.default_rng(13)
    # host tier sized for ~1 block so spills demote to disk immediately
    eng = make_engine(model, params, kv_blocks=14, host_bytes=9000,
                      disk_path=str(tmp_path), disk_bytes=1 << 20)
    reqs = shared_prefix_reqs(rng)
    g_ref = greedy_generate(make_engine(model, params, tier=False), reqs,
                            uid_base=300, max_new_tokens=4)
    sched = ContinuousBatchingScheduler(eng)
    for i, p in enumerate(reqs[:6]):
        sched.submit(300 + i, p, max_new_tokens=4)
        sched.run_to_completion()
    assert eng.tier_stats()["disk_blocks"] > 0
    for f in os.listdir(tmp_path):      # corrupt EVERY spill file
        if f.endswith(".swp"):
            with open(os.path.join(tmp_path, f), "r+b") as fh:
                fh.seek(0)
                fh.write(b"\xde\xad\xbe\xef" * 4)
    gens = []
    for i, p in enumerate(reqs[6:]):
        sched.submit(400 + i, p, max_new_tokens=4)
        sched.run_to_completion()
        gens.append(sched.finished[400 + i].generated)
    assert gens == g_ref[6:]            # re-prefilled, never crashed
    assert eng.tier_stats()["corrupt"] > 0


# ----------------------------------------------------------- disabled parity
def test_disabled_path_byte_identical(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(14)
    reqs = shared_prefix_reqs(rng)
    g_plain = greedy_generate(make_engine(model, params, tier=False),
                              reqs, uid_base=500, max_new_tokens=4)
    # config present but disabled: identical engine behavior
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=4,
        max_chunk_tokens=32, kv_blocks=14, kv_block_size=BS,
        max_tracked_sequences=64, enable_prefix_cache=True,
        kv_tier_enabled=False)
    g_dis = greedy_generate(InferenceEngineV2(model, params=params,
                                              config=vcfg),
                            reqs, uid_base=500, max_new_tokens=4)
    assert g_dis == g_plain


def test_configure_host_bytes_preserves_disk_tier(model_and_params,
                                                  tmp_path):
    """Re-tuning only the host bound must not silently destroy a
    configured disk tier (None arguments preserve config values)."""
    model, params = model_and_params
    eng = make_engine(model, params, tier=True,
                      disk_path=str(tmp_path), disk_bytes=1 << 20)
    eng.configure_kv_tier(True, host_bytes=128 << 20)
    assert eng.config.kv_tier_host_bytes == 128 << 20
    assert eng.config.kv_tier_disk_path == str(tmp_path)
    assert eng.config.kv_tier_disk_bytes == 1 << 20
    assert eng.state_manager._tier._swapper is not None
    # explicit drop: disk_bytes=0
    eng.configure_kv_tier(True, disk_bytes=0)
    assert eng.state_manager._tier._swapper is None


def test_tier_requires_prefix_cache(model_and_params):
    model, params = model_and_params
    eng = make_engine(model, params, tier=False, prefix=False)
    with pytest.raises(ValueError, match="prefix cache"):
        eng.configure_kv_tier(True)
    # the rejected configure must not leave config claiming a tier the
    # manager never built (an engine rebuilt from it would raise)
    assert not eng.config.kv_tier_enabled
    assert not eng.state_manager.kv_tier_enabled


def test_disabling_prefix_cache_tears_down_tier(model_and_params):
    model, params = model_and_params
    eng = make_engine(model, params, tier=True)
    assert eng.state_manager.kv_tier_enabled
    eng.configure_prefix_cache(False)
    assert not eng.state_manager.kv_tier_enabled
    assert not eng.config.kv_tier_enabled


def test_occupancy_carries_tier_fields(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(15)
    for tier in (False, True):
        occ = make_engine(model, params, tier=tier).occupancy()
        for k in ("kv_blocks_host_tier", "kv_bytes_host_tier",
                  "kv_blocks_disk_tier", "kv_bytes_disk_tier"):
            assert isinstance(occ[k], int) and occ[k] == 0
    eng = make_engine(model, params, tier=True)
    sched = ContinuousBatchingScheduler(eng)
    for i, p in enumerate(shared_prefix_reqs(rng)):
        sched.submit(600 + i, p, max_new_tokens=3)
        sched.run_to_completion()
    occ = eng.occupancy()
    assert occ["kv_blocks_host_tier"] > 0
    assert occ["kv_bytes_host_tier"] > 0


# --------------------------------------------------------------- serving e2e
def serving_config(**kv_tier):
    from deepspeed_tpu.serving import ServingConfig

    return ServingConfig(max_queue_depth=64,
                         prefix_cache={"enabled": True},
                         kv_tier=(kv_tier or {"enabled": True}),
                         # reservation admission makes small-pool
                         # concurrency safe (docs/SERVING.md "Admission
                         # and preemption"), so these tests no longer
                         # have to size max_seqs below the pool
                         admission={"reservation": True})


def test_frontend_applies_tier_and_publishes_metrics(model_and_params):
    from deepspeed_tpu.serving import ServingFrontend

    model, params = model_and_params
    rng = np.random.default_rng(16)
    reqs = shared_prefix_reqs(rng)
    eng = make_engine(model, params, tier=False, prefix=False)
    fe = ServingFrontend([eng], serving_config())
    try:
        assert eng.state_manager.kv_tier_enabled     # config applied it
        handles = [fe.submit(p, max_new_tokens=4) for p in reqs]
        assert fe.wait_all(handles, timeout=120)
        snap = fe.metrics_snapshot()
        assert snap["kv_tier_blocks_spilled"] > 0
        assert snap["kv_tier_blocks_restored"] > 0
        assert snap["kv_blocks_host_tier"] > 0
        assert snap["kv_tier_bytes_host"] > 0
        assert snap["kv_tier_restore_s"]["count"] > 0
        # pressure events land in the ops journal (bypass the ~1s gate)
        fe._tier_journal_t = -10.0
        fe._maybe_journal_tier_pressure()
        assert fe.journal.count("kv_tier_pressure") >= 1
        ev = fe.journal.events(kinds=("kv_tier_pressure",))[-1]
        assert ev["detail"]["spilled"] > 0
        rep = fe.health_report(window_s=60.0)
        assert rep["occupancy"]["kv_blocks_host_tier"] > 0
        assert "kv_tier_restore_s" in rep["window"]
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_frontend_disabled_block_is_byte_identical(model_and_params):
    """``kv_tier: {enabled: false}`` through the frontend's config is the
    tier-less stack: no tier on the engine, the same greedy streams as a
    config that has no ``kv_tier`` block."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    model, params = model_and_params
    reqs = shared_prefix_reqs(np.random.default_rng(16))

    def gens(extra):
        eng = make_engine(model, params, tier=False, prefix=False)
        fe = ServingFrontend([eng], ServingConfig(
            max_queue_depth=64, prefix_cache={"enabled": True},
            admission={"reservation": True}, **extra))
        try:
            assert not eng.state_manager.kv_tier_enabled
            handles = [fe.submit(p, max_new_tokens=4) for p in reqs]
            assert fe.wait_all(handles, timeout=120)
            return [[ev.token for ev in h.drain()] for h in handles]
        finally:
            fe.shutdown(drain=False, timeout=5)

    assert gens({"kv_tier": {"enabled": False}}) == gens({})


def test_restore_races_cancel_and_deadline(model_and_params):
    """Cancels and deadline expiries racing tier restores must settle
    terminally with the KV pool fully reclaimed — a restored block whose
    request dies goes back through the normal flush/eviction path."""
    from deepspeed_tpu.serving import RequestState, ServingFrontend

    model, params = model_and_params
    rng = np.random.default_rng(17)
    reqs = shared_prefix_reqs(rng, n_req=10)
    eng = make_engine(model, params, tier=False, prefix=False,
                      kv_blocks=24)
    fe = ServingFrontend([eng], serving_config())
    try:
        warm = [fe.submit(p, max_new_tokens=3) for p in reqs]
        assert fe.wait_all(warm, timeout=120)       # tier now warm
        handles = []
        for i, p in enumerate(reqs):
            if i % 3 == 2:
                h = fe.submit(p, max_new_tokens=40, deadline_ms=1.0)
            else:
                h = fe.submit(p, max_new_tokens=40)
            handles.append(h)
            if i % 3 == 0:
                fe.cancel(h)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and any(
                h.state in (RequestState.QUEUED, RequestState.RUNNING)
                for h in handles):
            time.sleep(0.02)
        assert all(h.state not in (RequestState.QUEUED,
                                   RequestState.RUNNING)
                   for h in handles), [h.state for h in handles]
    finally:
        fe.shutdown(drain=False, timeout=5)
    # all sequence KV returned; only the prefix cache's refs remain
    occ = eng.occupancy()
    assert occ["available_blocks"] == occ["total_blocks"]


def test_restore_survives_replica_death(model_and_params):
    """A replica crash mid-burst with the tier active: requests fail
    over and resume on the replacement with streams matching an
    unfaulted tier run (the PR 5 failover path composes with restores)."""
    from deepspeed_tpu.serving import (RequestState, ServingConfig,
                                       ServingFrontend)

    model, params = model_and_params
    rng = np.random.default_rng(18)
    reqs = shared_prefix_reqs(rng, n_req=8)

    def factory(i):
        return make_engine(model, params, tier=False, prefix=False,
                           max_seqs=2)

    def run(faulted):
        scfg = ServingConfig(
            max_queue_depth=64,
            prefix_cache={"enabled": True},
            kv_tier={"enabled": True},
            fault_tolerance={"enabled": True, "max_retries": 3,
                             "restart_backoff_s": 0.05,
                             "supervisor_poll_s": 0.02},
            faults=({"enabled": True, "schedule": [
                {"kind": "crash", "replica": 0, "at_step": 4}]}
                if faulted else {"enabled": False}))
        fe = ServingFrontend([factory(0)], scfg, engine_factory=factory)
        try:
            handles = [fe.submit(p, max_new_tokens=5) for p in reqs]
            assert fe.wait_all(handles, timeout=180)
            states = [h.state for h in handles]
            gens = [[ev.token for ev in h.drain()] for h in handles]
        finally:
            fe.shutdown(drain=False, timeout=5)
        assert all(s == RequestState.FINISHED for s in states), states
        return gens

    assert run(True) == run(False)


# --------------------------------------------------------------- config
def test_kv_tier_config_apply():
    kt = KVTierConfig(enabled=True, host_max_bytes=123, disk_path="/x",
                      disk_max_bytes=456)
    vcfg = RaggedInferenceEngineConfig()
    kt.apply(vcfg)
    assert vcfg.kv_tier_enabled
    assert vcfg.kv_tier_host_bytes == 123
    assert vcfg.kv_tier_disk_path == "/x"
    assert vcfg.kv_tier_disk_bytes == 456


def test_ds_config_mounts_kv_tier():
    from deepspeed_tpu.runtime.config import DeepSpeedTpuConfig

    c = DeepSpeedTpuConfig(**{"train_micro_batch_size_per_gpu": 1,
                              "kv_tier": {"enabled": True,
                                          "host_max_bytes": 99},
                              "serving": {"kv_tier": {"enabled": True}}})
    assert c.kv_tier.enabled and c.kv_tier.host_max_bytes == 99
    assert c.serving.kv_tier.enabled
