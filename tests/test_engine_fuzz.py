"""Seeded draws over random configurations: the engine against its
references.

``draw_case`` draws a ``SimConfig`` — pattern, topology, group
placement/stride, L1/L2 geometry, PTW width, optimization probes, message
sizes from sub-page to multi-GB — from a ``random.Random``.  Tier-1 runs
24 fixed draws (``random.Random(i)`` for case ``i``), each a parametrized
case: the engine against the reference DES where the exact-count contract
is established (DESIGN.md §7), else against ``RECORDED_DRAWS[i]``, the
output of the per-epoch scalar engine this engine was ported from,
recorded before that engine was retired (``test_engine_diff.summarize``).
Strided group placements are checked against the DES's ``RefSession`` on
the same calls.

The CI slow tier (``-m slow``) additionally runs a Hypothesis variant over
the same draws, moved into the DES contract and held to the DES, with
``ENGINE_DIFF_EXAMPLES`` (default 200) examples.  Found a disagreement?
Pin the config into ``test_engine_diff.CORPUS`` before fixing the engine.
"""
import dataclasses
import os
import random

import pytest

from repro.core import (RefSession, SimSession, paper_config, simulate,
                        simulate_ref, KB, MB, GB)
from repro.core.config import (FabricConfig, PreTranslationConfig,
                               PrefetchConfig, SimConfig, TLBConfig,
                               TranslationConfig)

from test_engine_diff import (PATTERN_NAMES, REF_MAX_BYTES,
                              assert_matches_ref, summarize)

N_DRAWS = 24
DEEP_EXAMPLES = int(os.environ.get("ENGINE_DIFF_EXAMPLES", "200"))


def draw_fabric(rng):
    topo = rng.choice(["single_clos", "two_tier", "multi_pod"])
    kw = dict(n_gpus=rng.choice([4, 8, 16]), topology=topo,
              ingress_entries=rng.choice([64, 256]))
    if topo == "two_tier":
        kw["leaf_size"] = rng.choice([0, 4])
        kw["oversubscription"] = rng.choice([1.0, 2.0, 4.0])
    elif topo == "multi_pod":
        kw["pod_size"] = rng.choice([0, 4])
        kw["interpod_oversubscription"] = rng.choice([1.0, 4.0])
    return FabricConfig(**kw)


def draw_translation(rng):
    if rng.random() < 0.5:
        return TranslationConfig()      # paper Table-1 defaults
    return TranslationConfig(
        l1=TLBConfig(entries=rng.choice([2, 8, 32]),
                     assoc=rng.choice([0, 2]),
                     hit_latency_ns=50.0, mshr_entries=256),
        l2=TLBConfig(entries=rng.choice([16, 128, 512]),
                     assoc=rng.choice([0, 2, 4]),
                     hit_latency_ns=100.0, mshr_entries=512),
        n_ptw=rng.choice([1, 4, 100]))


def draw_case(rng):
    """One ``(nbytes, cfg)`` draw."""
    cfg = SimConfig(
        fabric=draw_fabric(rng),
        translation=draw_translation(rng),
        collective=rng.choice(PATTERN_NAMES),
        iterations=rng.choice([1, 2]),
        symmetric=rng.random() < 0.5)
    opt = rng.choice(["none", "none", "pretranslate", "prefetch"])
    if opt == "pretranslate":
        cfg = cfg.replace(pretranslation=PreTranslationConfig(
            enabled=True,
            lead_time_ns=rng.choice([1000.0, 3000.0]),
            pages_per_flow=rng.choice([0, 1])))
    elif opt == "prefetch":
        cfg = cfg.replace(prefetch=PrefetchConfig(
            enabled=True, depth=rng.choice([1, 2])))
    if rng.random() < 0.5:
        nbytes = rng.randint(1 * KB, 4 * MB)
    else:
        nbytes = rng.choice([4 * KB, 1 * MB, 16 * MB, 2 * GB])
    if nbytes <= REF_MAX_BYTES:
        # Trace arrays are per-request: keep them off multi-GB draws.
        cfg = cfg.replace(collect_trace=rng.random() < 0.5)
    return nbytes, cfg


def in_ref_contract(nbytes, cfg) -> bool:
    """Where the engine/DES exact-count contract is established:
    paper-default translation and ingress, one iteration (DESIGN.md §7)."""
    return (nbytes <= REF_MAX_BYTES and cfg.iterations == 1
            and cfg.translation == TranslationConfig()
            and cfg.fabric.ingress_entries == 256)


# Recorded outputs of the draws outside the DES contract (see the module
# docstring), keyed by case index.
RECORDED_DRAWS = {
    0: dict(
        completion_ns=49645.920000000006,
        iterations=[49645.920000000006, 45382.4],
        counters=dict(requests=524288, by_class=dict(l1_hit=488884,
            l1_mshr_hum=34892, l2_hit=0, l2_hum=480, walk=32),
            rat_ns_sum=41960891.84000006, rat_ns_max=1700.0, walks=32,
            walk_mem_reads=48, pwc_hits=112, pwc_misses=16, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.5767187500000017,
        trace='d8d2e3612bd7ac06'),
    1: dict(
        completion_ns=10834.64,
        iterations=[10834.64, 9259.52],
        counters=dict(requests=3752, by_class=dict(l1_hit=3283,
            l1_mshr_hum=453, l2_hit=0, l2_hum=15, walk=1),
            rat_ns_sum=943890.64, rat_ns_max=1700.0, walks=1, walk_mem_reads=5,
            pwc_hits=0, pwc_misses=4, probes=0, mshr_stall_ns=0.0),
        mean_stall_ns=0.0),
    2: dict(
        completion_ns=7780.0,
        iterations=[7780.0, 5790.399999999998],
        counters=dict(requests=24576, by_class=dict(l1_hit=12288,
            l1_mshr_hum=12240, l2_hit=0, l2_hum=45, walk=3),
            rat_ns_sum=17478451.200000003, rat_ns_max=1700.0, walks=3,
            walk_mem_reads=15, pwc_hits=0, pwc_misses=12, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=2.99796875),
    3: dict(
        completion_ns=3046788.4799999697,
        iterations=[3046788.4799999697, 2986077.120000137],
        counters=dict(requests=503316480, by_class=dict(l1_hit=393125528,
            l1_mshr_hum=109699432, l2_hit=0, l2_hum=460800, walk=30720),
            rat_ns_sum=56627562457.608635, rat_ns_max=1700.0, walks=30720,
            walk_mem_reads=30800, pwc_hits=122800, pwc_misses=80, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.4461370849610538),
    4: dict(
        completion_ns=8225.44,
        iterations=[8225.44, 6239.200000000001],
        counters=dict(requests=176004, by_class=dict(l1_hit=118856,
            l1_mshr_hum=56796, l2_hit=0, l2_hum=330, walk=22),
            rat_ns_sum=38948073.59999996, rat_ns_max=1700.0, walks=22,
            walk_mem_reads=54, pwc_hits=56, pwc_misses=32, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=1.6929360696347808,
        trace='c058bd92dd399e8c'),
    5: dict(
        completion_ns=8374.8,
        iterations=[8374.8],
        counters=dict(requests=48, by_class=dict(l1_hit=32, l1_mshr_hum=0,
            l2_hit=0, l2_hum=15, walk=1), rat_ns_sum=28780.8,
            rat_ns_max=1700.0, walks=1, walk_mem_reads=5, pwc_hits=0,
            pwc_misses=4, probes=0, mshr_stall_ns=0.0),
        mean_stall_ns=0.0),
    6: dict(
        completion_ns=16682.4,
        iterations=[16682.4, 16593.600000000013],
        counters=dict(requests=157740, by_class=dict(l1_hit=137546,
            l1_mshr_hum=20071, l2_hit=0, l2_hum=120, walk=3),
            rat_ns_sum=13500987.600000001, rat_ns_max=620.0, walks=8,
            walk_mem_reads=28, pwc_hits=12, pwc_misses=20, probes=10,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.0,
        trace='e8703f6801b881fd'),
    7: dict(
        completion_ns=4367.04,
        iterations=[4367.04],
        counters=dict(requests=6778, by_class=dict(l1_hit=1993,
            l1_mshr_hum=4754, l2_hit=0, l2_hum=30, walk=1),
            rat_ns_sum=3615547.7599999933, rat_ns_max=1700.0, walks=2,
            walk_mem_reads=10, pwc_hits=0, pwc_misses=8, probes=1,
            mshr_stall_ns=0.0),
        mean_stall_ns=2.4574564768368248,
        trace='000735dc6a82d024'),
    9: dict(
        completion_ns=57064.400000000016,
        iterations=[57064.400000000016, 53901.5999999999],
        counters=dict(requests=460830, by_class=dict(l1_hit=434149,
            l1_mshr_hum=26169, l2_hit=0, l2_hum=480, walk=32),
            rat_ns_sum=52957326.0, rat_ns_max=1700.0, walks=32,
            walk_mem_reads=100, pwc_hits=60, pwc_misses=68, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.0),
    10: dict(
        completion_ns=7415.28,
        iterations=[7415.28, 7050.56],
        counters=dict(requests=128544, by_class=dict(l1_hit=96408,
            l1_mshr_hum=32072, l2_hit=0, l2_hum=60, walk=4),
            rat_ns_sum=38799720.95999999, rat_ns_max=1700.0, walks=4,
            walk_mem_reads=20, pwc_hits=0, pwc_misses=16, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.5195120736868309,
        trace='3ae409ca800a93bb'),
    11: dict(
        completion_ns=103200.0,
        iterations=[103200.0],
        counters=dict(requests=30, by_class=dict(l1_hit=30, l1_mshr_hum=0,
            l2_hit=0, l2_hum=0, walk=0), rat_ns_sum=1500.0, rat_ns_max=50.0,
            walks=1, walk_mem_reads=5, pwc_hits=0, pwc_misses=4, probes=1,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.0),
    12: dict(
        completion_ns=5794.08,
        iterations=[5794.08, 4749.4400000000005],
        counters=dict(requests=32768, by_class=dict(l1_hit=22936,
            l1_mshr_hum=9768, l2_hit=0, l2_hum=60, walk=4),
            rat_ns_sum=7491596.159999981, rat_ns_max=1700.0, walks=4,
            walk_mem_reads=20, pwc_hits=0, pwc_misses=16, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=2.0215625),
    13: dict(
        completion_ns=7434.48,
        iterations=[7434.48],
        counters=dict(requests=20, by_class=dict(l1_hit=3, l1_mshr_hum=6,
            l2_hit=5, l2_hum=5, walk=1), rat_ns_sum=21291.36,
            rat_ns_max=1700.0, walks=1, walk_mem_reads=5, pwc_hits=0,
            pwc_misses=4, probes=0, mshr_stall_ns=0.0),
        mean_stall_ns=0.0,
        trace='af8bfbccab67c602'),
    14: dict(
        completion_ns=6064.8,
        iterations=[6064.8, 4528.64],
        counters=dict(requests=228880, by_class=dict(l1_hit=158544,
            l1_mshr_hum=70088, l2_hit=120, l2_hum=120, walk=8),
            rat_ns_sum=76874353.91999991, rat_ns_max=1700.0, walks=16,
            walk_mem_reads=80, pwc_hits=0, pwc_misses=64, probes=16,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.8584187347081441,
        trace='87c2ba4a7770e599'),
    15: dict(
        completion_ns=13455.04,
        iterations=[13455.04],
        counters=dict(requests=49152, by_class=dict(l1_hit=39732,
            l1_mshr_hum=9324, l2_hit=0, l2_hum=90, walk=6),
            rat_ns_sum=6999531.839999994, rat_ns_max=1700.0, walks=6,
            walk_mem_reads=10, pwc_hits=20, pwc_misses=4, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=1.2377636718749985,
        trace='bda7068dcdfd75b9'),
    16: dict(
        completion_ns=25466.560000000005,
        iterations=[25466.560000000005, 25466.559999999976],
        counters=dict(requests=69440, by_class=dict(l1_hit=65120,
            l1_mshr_hum=4200, l2_hit=30, l2_hum=90, walk=0),
            rat_ns_sum=5104201.6, rat_ns_max=530.0, walks=8, walk_mem_reads=40,
            pwc_hits=0, pwc_misses=32, probes=16, mshr_stall_ns=0.0),
        mean_stall_ns=0.0),
    17: dict(
        completion_ns=36338.96,
        iterations=[36338.96, 35757.11999999999],
        counters=dict(requests=23380, by_class=dict(l1_hit=20819,
            l1_mshr_hum=2529, l2_hit=0, l2_hum=30, walk=2),
            rat_ns_sum=3286699.6000000015, rat_ns_max=1700.0, walks=2,
            walk_mem_reads=6, pwc_hits=4, pwc_misses=4, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.0),
    18: dict(
        completion_ns=4417.36,
        iterations=[4417.36, 3141.4399999999996],
        counters=dict(requests=45102, by_class=dict(l1_hit=27806,
            l1_mshr_hum=17184, l2_hit=0, l2_hum=105, walk=7),
            rat_ns_sum=11947764.799999975, rat_ns_max=1700.0, walks=7,
            walk_mem_reads=26, pwc_hits=9, pwc_misses=19, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=1.7256600594208682),
    19: dict(
        completion_ns=9441.36,
        iterations=[9441.36, 8623.200000000004],
        counters=dict(requests=95190, by_class=dict(l1_hit=74978,
            l1_mshr_hum=20088, l2_hit=0, l2_hum=120, walk=4),
            rat_ns_sum=10232204.559999995, rat_ns_max=620.0, walks=8,
            walk_mem_reads=24, pwc_hits=16, pwc_misses=16, probes=8,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.5513587561718666,
        trace='ae0ef09052a67176'),
    20: dict(
        completion_ns=6065.76,
        iterations=[6065.76],
        counters=dict(requests=3075, by_class=dict(l1_hit=2745,
            l1_mshr_hum=314, l2_hit=0, l2_hum=15, walk=1),
            rat_ns_sum=449122.00000000047, rat_ns_max=1700.0, walks=1,
            walk_mem_reads=5, pwc_hits=0, pwc_misses=4, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=7.899369105691058,
        trace='7258087438370594'),
    21: dict(
        completion_ns=21220.0,
        iterations=[21220.0],
        counters=dict(requests=67207, by_class=dict(l1_hit=49409,
            l1_mshr_hum=17542, l2_hit=0, l2_hum=240, walk=16),
            rat_ns_sum=22279113.68, rat_ns_max=2010.6399999999999, walks=16,
            walk_mem_reads=48, pwc_hits=32, pwc_misses=32, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.0,
        trace='113d9a4319e63a30'),
    22: dict(
        completion_ns=5390.32,
        iterations=[5390.32],
        counters=dict(requests=19710, by_class=dict(l1_hit=5798,
            l1_mshr_hum=13848, l2_hit=0, l2_hum=60, walk=4),
            rat_ns_sum=10537566.240000015, rat_ns_max=1700.0, walks=4,
            walk_mem_reads=12, pwc_hits=8, pwc_misses=8, probes=0,
            mshr_stall_ns=0.0),
        mean_stall_ns=3.2296783358701164,
        trace='35b1afcfd44c7d1d'),
    23: dict(
        completion_ns=3798.8,
        iterations=[3798.8],
        counters=dict(requests=14920, by_class=dict(l1_hit=11186,
            l1_mshr_hum=3706, l2_hit=0, l2_hum=28, walk=0),
            rat_ns_sum=1508821.920000002, rat_ns_max=530.0, walks=2,
            walk_mem_reads=10, pwc_hits=0, pwc_misses=8, probes=4,
            mshr_stall_ns=0.0),
        mean_stall_ns=0.7801823056300268),
}


@pytest.mark.parametrize("case", range(N_DRAWS))
def test_seeded_draws(case):
    nbytes, cfg = draw_case(random.Random(case))
    a = simulate(nbytes, cfg)
    if in_ref_contract(nbytes, cfg):
        assert_matches_ref(a, simulate_ref(nbytes, cfg))
    else:
        assert summarize(a) == RECORDED_DRAWS[case]


def draw_placement(rng):
    """(group, stride, pattern, nbytes) of a strided subgroup inside a
    16-GPU pod."""
    group = rng.choice([4, 8, 16])
    max_stride = (16 - 1) // max(group - 1, 1)
    stride = rng.randint(1, max(1, max_stride))
    return (group, stride, rng.choice(PATTERN_NAMES),
            rng.choice([64 * KB, 1 * MB]))


@pytest.mark.parametrize("case", range(12))
def test_fuzz_group_placement(case):
    """Subgroups on strided pod ranks: cold + warm calls through the
    engine's session and the DES's session, per-call counts exactly
    equal, completions within the DES tolerance."""
    group, stride, name, nbytes = draw_placement(random.Random(case))
    cfg = paper_config(16)
    eng, ref = SimSession(cfg), RefSession(cfg)
    for s in (eng, ref):
        for _ in range(2):
            s.run(nbytes, collective=name, n_gpus=group,
                  rank_stride=stride)
    for a, r in zip(eng.records, ref.records):
        assert a.counters.requests == r.counters.requests
        assert a.counters.walks == r.counters.walks
        assert a.completion_ns == pytest.approx(r.completion_ns, rel=0.05)


try:
    from hypothesis import given, settings, strategies as st
except ImportError:     # hypothesis is an optional dev dependency
    given = None

if given is not None:
    @pytest.mark.slow
    @settings(max_examples=DEEP_EXAMPLES, deadline=None)
    @given(st.randoms(use_true_random=False))
    def test_fuzz_engines_agree_deep(rng):
        """The CI slow tier's deep variant: draws moved into the DES
        contract (default translation and ingress, one iteration, at most
        ``REF_MAX_BYTES``) and held to the DES."""
        nbytes, cfg = draw_case(rng)
        cfg = cfg.replace(
            translation=TranslationConfig(), iterations=1,
            fabric=dataclasses.replace(cfg.fabric, ingress_entries=256))
        nbytes = min(nbytes, REF_MAX_BYTES)
        assert_matches_ref(simulate(nbytes, cfg), simulate_ref(nbytes, cfg))
