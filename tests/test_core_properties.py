"""Hypothesis property tests for the RAT simulator (repro.core).

Kept separate from ``test_core_sim.py`` so the main suite still collects when
``hypothesis`` is not installed — this module degrades to a skip.  Every
property runs derandomized with no example database, so tier-1 checks the
same examples on every run.
"""
import dataclasses
import math

import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import ratsim, paper_config, simulate, MB  # noqa: E402
from repro.core.config import TLBConfig  # noqa: E402


@settings(max_examples=25, deadline=None, derandomize=True, database=None)
@given(size_mb=st.sampled_from([1, 2, 4, 8, 16, 64]),
       n=st.sampled_from([8, 16, 32]))
def test_property_baseline_never_faster_than_ideal(size_mb, n):
    c = ratsim.compare(size_mb * MB, n)
    assert c.degradation >= 1.0 - 1e-12


@settings(max_examples=15, deadline=None, derandomize=True, database=None)
@given(size_mb=st.sampled_from([1, 4, 16]), n=st.sampled_from([8, 16, 32]))
def test_property_request_conservation(size_mb, n):
    r = ratsim.run(size_mb * MB, n)
    ctr = r.counters
    assert sum(ctr.by_class.values()) == ctr.requests
    fab = r.config.fabric
    chunk = (size_mb * MB) // n
    expected = (fab.n_gpus - 1) * math.ceil(chunk / fab.request_bytes)
    assert ctr.requests == expected


@settings(max_examples=10, deadline=None, derandomize=True, database=None)
@given(entries=st.sampled_from([64, 512, 4096]))
def test_property_bigger_l2_never_hurts(entries):
    cfg = paper_config(16)
    tr = dataclasses.replace(
        cfg.translation,
        l2=TLBConfig(entries=entries, assoc=2, hit_latency_ns=100.0,
                     mshr_entries=512))
    big = simulate(4 * MB, cfg.replace(translation=tr)).completion_ns
    tr_small = dataclasses.replace(
        cfg.translation,
        l2=TLBConfig(entries=16, assoc=2, hit_latency_ns=100.0,
                     mshr_entries=512))
    small = simulate(4 * MB, cfg.replace(translation=tr_small)).completion_ns
    assert big <= small * (1 + 1e-9)


@settings(max_examples=10, deadline=None, derandomize=True, database=None)
@given(n=st.sampled_from([8, 16, 32, 64]))
def test_property_ideal_completion_is_bandwidth_bound(n):
    size = 64 * MB
    cfg = paper_config(n).ideal()
    r = simulate(size, cfg)
    fab = cfg.fabric
    chunk = size // n
    n_req = math.ceil(chunk / fab.request_bytes)
    stream = (n_req - 1) * fab.request_bytes * (n - 1) / fab.gpu_bw
    expected = fab.oneway_ns + stream + fab.hbm_ns + fab.return_ns
    assert r.completion_ns == pytest.approx(expected, rel=1e-6)


@settings(max_examples=15, deadline=None, derandomize=True, database=None)
@given(coll=st.sampled_from(["ring_allreduce", "rd_allreduce", "all_gather",
                             "reduce_scatter", "broadcast",
                             "hier_all_to_all"]),
       size_mb=st.sampled_from([1, 4, 16]))
def test_property_patterns_never_faster_than_ideal(coll, size_mb):
    c = ratsim.compare(size_mb * MB, 16, collective=coll)
    assert c.degradation >= 1.0 - 1e-12


# ------------------------------------------------------- session properties
from repro.core import SimSession, simulate  # noqa: E402
from repro.core.config import FabricConfig, PrefetchConfig  # noqa: E402


@settings(max_examples=20, deadline=None, derandomize=True, database=None)
@given(size_mb=st.sampled_from([1, 2, 4, 16]),
       n=st.sampled_from([8, 16, 32]),
       coll=st.sampled_from(["all_to_all", "ring_allreduce", "all_gather",
                             "broadcast"]))
def test_property_warm_rerun_never_slower(size_mb, n, coll):
    """A second identical collective on a warm session is never slower."""
    s = SimSession(paper_config(n).replace(collective=coll))
    cold = s.run(size_mb * MB)
    warm = s.run(size_mb * MB)
    assert warm.completion_ns <= cold.completion_ns + 1e-9


@settings(max_examples=15, deadline=None, derandomize=True, database=None)
@given(size_mb=st.sampled_from([1, 4, 16]), k=st.sampled_from([1, 2, 3]),
       n=st.sampled_from([8, 16]))
def test_property_session_replay_equals_iterations(size_mb, k, n):
    """k session runs == one simulate(iterations=k) for the default
    all-to-all, bit for bit."""
    sess = SimSession(paper_config(n))
    for _ in range(k):
        sess.run(size_mb * MB)
    got = sess.result()
    one = simulate(size_mb * MB, paper_config(n).replace(iterations=k))
    assert ([i.completion_ns for i in got.iterations]
            == [i.completion_ns for i in one.iterations])
    assert got.counters.by_class == one.counters.by_class
    assert got.counters.walks == one.counters.walks


@settings(max_examples=10, deadline=None, derandomize=True, database=None)
@given(size_mb=st.sampled_from([16, 64]), depth=st.sampled_from([1, 2, 3]))
def test_property_prefetch_depth_monotone_under_scarce_buffering(size_mb,
                                                                 depth):
    """Deeper next-page prefetch never slows a scarce-ingress collective:
    disabled >= depth d >= depth d+1 (more pages warmed ahead of the
    stream can only remove port stalls)."""
    fab = FabricConfig(n_gpus=16, ingress_entries=64)
    cfg = paper_config(16).replace(fabric=fab)
    off = simulate(size_mb * MB, cfg).completion_ns
    shallow = simulate(size_mb * MB, cfg.replace(
        prefetch=PrefetchConfig(enabled=True, depth=depth))).completion_ns
    deep = simulate(size_mb * MB, cfg.replace(
        prefetch=PrefetchConfig(enabled=True, depth=depth + 1))).completion_ns
    assert shallow <= off + 1e-9
    assert deep <= shallow + 1e-9
