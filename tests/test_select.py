"""Tests for RAT-aware collective algorithm selection (repro.core.select).

Covers the registry side (logical equivalence classes populated by
@register_pattern, feasibility-filtered candidate enumeration), the three
policies (fixed defaults bit-for-bit, exhaustive auto pricing, the
serializable PolicyTable with fixed fallback), the spec-string parser, and
the threading through every consumer layer: sessions (engine and oracle),
ratsim sweeps (eager axis validation), workload derivation (provenance on
every call) and request-level serving.
"""
import json

import pytest

from repro.core import KB, MB, ratsim, simulate
from repro.core.config import FabricConfig, SimConfig, TranslationConfig
from repro.core.patterns import (LOGICAL, PATTERNS, candidates_for,
                                 get_pattern, logical_of, register_pattern)
from repro.core.ref_des import RefSession
from repro.core.select import (FIXED_DEFAULTS, AutoPolicy, FixedPolicy,
                               PolicyTable, Resolution, build_policy_table,
                               get_policy, size_bucket)
from repro.core.session import SimSession


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_logical_classes_cover_registry(self):
        # Every registered pattern belongs to exactly one logical class.
        members = [n for cls in LOGICAL.values() for n in cls]
        assert sorted(members) == sorted(PATTERNS)
        assert LOGICAL["allreduce"] == ["ring_allreduce", "rd_allreduce"]
        assert LOGICAL["all_to_all"] == ["all_to_all", "hier_all_to_all",
                                         "multipod_all_to_all"]

    def test_logical_of(self):
        assert logical_of("rd_allreduce") == "allreduce"
        assert logical_of("all_to_all") == "all_to_all"
        with pytest.raises(ValueError, match="unknown collective"):
            logical_of("bogus")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            @register_pattern
            class Dup(PATTERNS["all_to_all"]):
                name = "all_to_all"

    def test_get_pattern_error_names_logical_classes(self):
        # A logical name is not a concrete pattern; the error must point
        # the caller at the policy layer rather than dead-end.
        with pytest.raises(ValueError, match="logical classes"):
            get_pattern("allreduce")

    def test_candidates_filtered_by_feasibility(self):
        # Recursive doubling needs power-of-two ranks.
        assert "rd_allreduce" in candidates_for(
            "allreduce", FabricConfig(n_gpus=8))
        assert candidates_for("allreduce", FabricConfig(n_gpus=6)) \
            == ["ring_allreduce"]

    def test_candidates_accept_concrete_name(self):
        fab = FabricConfig(n_gpus=8)
        assert candidates_for("rd_allreduce", fab) \
            == candidates_for("allreduce", fab)

    def test_candidates_unknown_name_raises(self):
        with pytest.raises(ValueError, match="logical classes"):
            candidates_for("bogus", FabricConfig(n_gpus=8))


# ---------------------------------------------------------------- policies
FAB8 = FabricConfig(n_gpus=8)


class TestFixedPolicy:
    def test_resolves_historical_defaults(self):
        pol = FixedPolicy()
        for logical, default in FIXED_DEFAULTS.items():
            res = pol.resolve(logical, 1 * MB, FAB8)
            assert res == Resolution(collective=default, logical=logical,
                                     provenance="fixed")

    def test_concrete_name_passes_through(self):
        res = FixedPolicy().resolve("rd_allreduce", 1 * MB, FAB8)
        assert res.collective == "rd_allreduce"
        assert res.logical == "allreduce"
        assert res.provenance == "explicit"

    def test_override_validated(self):
        pol = FixedPolicy(overrides={"allreduce": "rd_allreduce"})
        assert pol.resolve("allreduce", 1 * MB, FAB8).collective \
            == "rd_allreduce"
        with pytest.raises(ValueError, match="unknown logical class"):
            FixedPolicy(overrides={"bogus": "ring_allreduce"})
        with pytest.raises(ValueError, match="not a member"):
            FixedPolicy(overrides={"allreduce": "all_gather"})

    def test_state_validated(self):
        with pytest.raises(ValueError, match="unknown TLB state"):
            FixedPolicy().resolve("allreduce", 1 * MB, FAB8, state="tepid")

    def test_unknown_collective_raises(self):
        with pytest.raises(ValueError, match="logical classes"):
            FixedPolicy().resolve("bogus", 1 * MB, FAB8)


class TestAutoPolicy:
    def test_picks_scored_minimum_per_state(self):
        auto = AutoPolicy()
        sc = auto.scores("allreduce", 1 * MB, FAB8)
        assert set(sc) == {"ring_allreduce", "rd_allreduce"}
        for si, state in enumerate(("cold", "warm")):
            res = auto.resolve("allreduce", 1 * MB, FAB8, state=state)
            assert res.provenance == f"auto:{state}"
            assert sc[res.collective][si] == min(v[si] for v in sc.values())

    def test_scores_match_direct_simulation(self):
        auto = AutoPolicy()
        sc = auto.scores("allreduce", 1 * MB, FAB8)
        cfg = SimConfig(fabric=FAB8, collective="ring_allreduce",
                        iterations=2, symmetric=True, collect_trace=False)
        r = simulate(1 * MB, cfg)
        assert sc["ring_allreduce"] == (r.iterations[0].completion_ns,
                                        r.iterations[1].completion_ns)

    def test_memoizes_per_size_fabric_and_base(self, monkeypatch):
        import repro.core.engine as engine_mod
        calls = []
        orig = engine_mod.simulate

        def counting(nbytes, cfg):
            calls.append(cfg.collective)
            return orig(nbytes, cfg)

        monkeypatch.setattr(engine_mod, "simulate", counting)
        auto = AutoPolicy()
        auto.resolve("allreduce", 256 * KB, FAB8, state="cold")
        auto.resolve("allreduce", 256 * KB, FAB8, state="warm")
        assert len(calls) == 2          # one pricing per candidate, reused

    def test_base_config_changes_pricing(self):
        # The deployment config (here: 4 KB pages) is part of the score —
        # the cold completion pays far more walks than the 2 MB default.
        small = AutoPolicy(base=SimConfig(
            translation=TranslationConfig(page_bytes=4 * KB)))
        default = AutoPolicy()
        s4k = small.scores("allreduce", 1 * MB, FAB8)
        s2m = default.scores("allreduce", 1 * MB, FAB8)
        assert s4k["ring_allreduce"][0] > s2m["ring_allreduce"][0]

    def test_no_feasible_candidate_raises(self):
        # hier/multipod all_to_all need divisible groups; on a 2-GPU flat
        # fabric only the direct form survives — but a logical class can
        # still empty out: allreduce on n=1 has no feasible member.
        with pytest.raises(ValueError, match="no feasible"):
            AutoPolicy().resolve("allreduce", 1 * MB, FabricConfig(n_gpus=1))


def _diverging_table(nbytes=1 * MB, fab=FAB8):
    """A hand-built table: cold -> rd, warm -> ring for one bucket."""
    t = PolicyTable()
    t.entries[t.key("allreduce", nbytes, fab, "cold")] = "rd_allreduce"
    t.entries[t.key("allreduce", nbytes, fab, "warm")] = "ring_allreduce"
    return t


class TestPolicyTable:
    def test_size_bucket(self):
        assert size_bucket(1 * MB) == 20
        assert size_bucket(2 * MB - 1) == 20
        assert size_bucket(2 * MB) == 21
        assert size_bucket(0) == 0

    def test_hit_and_miss_resolution(self):
        t = _diverging_table()
        assert t.resolve("allreduce", 1 * MB, FAB8, "cold") == Resolution(
            "rd_allreduce", "allreduce", "table:cold")
        assert t.resolve("allreduce", 1 * MB, FAB8, "warm") == Resolution(
            "ring_allreduce", "allreduce", "table:warm")
        # Outside the table: fixed defaults, flagged as a miss.
        miss = t.resolve("allreduce", 64 * MB, FAB8, "cold")
        assert miss.collective == FIXED_DEFAULTS["allreduce"]
        assert miss.provenance == "table:miss"
        miss = t.resolve("all_gather", 1 * MB, FAB8, "cold")
        assert miss.provenance == "table:miss"

    def test_save_load_round_trip(self, tmp_path):
        t = build_policy_table([256 * KB, 1 * MB], [8],
                               logicals=("allreduce",))
        path = tmp_path / "table.json"
        t.save(str(path))
        back = PolicyTable.load(str(path))
        assert back.entries == t.entries
        assert back.meta == t.meta
        # get_policy's spec-string form loads the same table.
        spec = get_policy(f"table:{path}")
        assert spec.entries == t.entries

    def test_load_rejects_wrong_schema_and_unknown_collective(self, tmp_path):
        with pytest.raises(ValueError, match="policy-table-v1"):
            PolicyTable.from_json({"schema": "bogus", "entries": []})
        doc = _diverging_table().to_json()
        doc["entries"][0]["collective"] = "bogus"
        path = tmp_path / "bad.json"
        path.write_text(json.dumps(doc))
        with pytest.raises(ValueError, match="unknown collective"):
            PolicyTable.load(str(path))

    def test_builder_caches_auto_optima(self):
        auto = AutoPolicy()
        t = build_policy_table([1 * MB], [8], logicals=("allreduce",),
                               auto=auto)
        for state in ("cold", "warm"):
            assert t.resolve("allreduce", 1 * MB, FAB8, state).collective \
                == auto.resolve("allreduce", 1 * MB, FAB8, state).collective
        assert t.meta["gpu_counts"] == [8]

    def test_builder_skips_infeasible_points(self):
        # n=6: no rd candidate, but ring still prices; n=1: nothing.
        t = build_policy_table([1 * MB], [6, 1], logicals=("allreduce",))
        assert t.entries[t.key("allreduce", 1 * MB, FabricConfig(n_gpus=6),
                               "cold")] == "ring_allreduce"
        assert not any(k[3] == 1 for k in t.entries)


class TestGetPolicy:
    def test_spec_strings(self):
        assert get_policy(None) is None
        pol = FixedPolicy()
        assert get_policy(pol) is pol
        assert isinstance(get_policy("fixed"), FixedPolicy)
        assert isinstance(get_policy("auto"), AutoPolicy)
        with pytest.raises(ValueError, match="unknown policy spec"):
            get_policy("bogus")


# ---------------------------------------------------------------- sessions
class TestSessionPolicy:
    def _cfg(self, **kw):
        return SimConfig(fabric=FAB8, **kw)

    def test_fixed_policy_is_bit_for_bit(self):
        # The same call sequence with and without the policy layer: the
        # fixed defaults must reproduce the pre-policy session exactly.
        plain = SimSession(self._cfg())
        fixed = SimSession(self._cfg(), policy="fixed")
        for sess, name in ((plain, "ring_allreduce"), (fixed, "allreduce")):
            for off in (0, 8 * MB):
                sess.run(1 * MB, collective=name, base_offset=off)
        for a, b in zip(plain.records, fixed.records):
            assert a.collective == b.collective == "ring_allreduce"
            assert a.t_end == b.t_end
            assert a.counters.walks == b.counters.walks

    def test_cold_warm_keyed_on_buffer_region(self):
        t = _diverging_table()
        sess = SimSession(self._cfg(), policy=t)
        first = sess.run(1 * MB, collective="allreduce")
        again = sess.run(1 * MB, collective="allreduce")
        other = sess.run(1 * MB, collective="allreduce", base_offset=32 * MB)
        assert first.collective == "rd_allreduce"      # region cold
        assert again.collective == "ring_allreduce"    # region warm
        assert other.collective == "rd_allreduce"      # new region cold

    def test_retention_flush_demotes_to_cold(self):
        t = _diverging_table()
        sess = SimSession(self._cfg(tlb_retention_ns=10_000.0), policy=t)
        assert sess.run(1 * MB, collective="allreduce").collective \
            == "rd_allreduce"
        assert sess.run(1 * MB, collective="allreduce",
                        gap_ns=1_000.0).collective == "ring_allreduce"
        # A gap past retention flushes the TLBs before resolution.
        assert sess.run(1 * MB, collective="allreduce",
                        gap_ns=50_000.0).collective == "rd_allreduce"

    def test_explicit_name_pins_under_any_policy(self):
        sess = SimSession(self._cfg(), policy=_diverging_table())
        rec = sess.run(1 * MB, collective="ring_allreduce")
        assert rec.collective == "ring_allreduce"

    def test_oracle_session_resolves_identically(self):
        # The oracle-equivalence contract extends to policy-chosen
        # algorithms: both sessions pick the same sequence and agree on
        # walks (and closely on completion).
        t = _diverging_table(256 * KB)
        cfg = SimConfig(fabric=FAB8)
        sim = SimSession(cfg, policy=t)
        ref = RefSession(cfg, policy=t)
        for _ in range(2):
            a = sim.run(256 * KB, collective="allreduce")
            b = ref.run(256 * KB, collective="allreduce")
            assert a.collective == b.collective
            assert a.counters.walks == b.counters.walks
            assert a.completion_ns == pytest.approx(b.completion_ns,
                                                    rel=0.05)
        assert [r.collective for r in sim.records] \
            == ["rd_allreduce", "ring_allreduce"]

    def test_ratsim_session_accepts_policy_spec(self):
        s = ratsim.session(8, policy="fixed")
        assert s.run(1 * MB, collective="allreduce").collective \
            == "ring_allreduce"


# ------------------------------------------------------- ratsim validation
class TestSweepValidation:
    def test_run_with_policy_matches_concrete(self):
        a = ratsim.run(1 * MB, 8, collective="allreduce", policy="fixed")
        b = ratsim.run(1 * MB, 8, collective="ring_allreduce")
        assert a.completion_ns == b.completion_ns
        assert a.counters.walks == b.counters.walks

    def test_sweep_rejects_unknown_collective_eagerly(self):
        with pytest.raises(ValueError, match="unknown collective 'bogus'"):
            ratsim.sweep([1 * MB], [8], collectives=["bogus"], workers=0)

    def test_sweep_rejects_unknown_topology_eagerly(self):
        with pytest.raises(ValueError, match="unknown topology"):
            ratsim.sweep([1 * MB], [8], topologies=["bogus"], workers=0)

    def test_sweep_logical_collective_needs_policy(self):
        with pytest.raises(ValueError, match="needs a policy"):
            ratsim.sweep([1 * MB], [8], collectives=["allreduce"], workers=0)

    def test_sweep_logical_collective_with_policy(self):
        got = ratsim.sweep([1 * MB], [8], collectives=["allreduce"],
                           policy="fixed", workers=0)
        ref = ratsim.sweep([1 * MB], [8], collectives=["ring_allreduce"],
                           workers=0)
        assert got[("allreduce", 8, 1 * MB)].baseline.completion_ns \
            == ref[("ring_allreduce", 8, 1 * MB)].baseline.completion_ns

    def test_run_logical_without_policy_raises(self):
        with pytest.raises(ValueError, match="logical classes"):
            ratsim.run(1 * MB, 8, collective="allreduce")


# ------------------------------------------------------- derivation layer
class TinyMoE:
    """Duck-typed stand-in for ModelConfig (only the fields derive reads)."""
    name = "tiny-moe"
    n_layers = 4
    d_model = 512
    n_heads = 8
    n_kv_heads = 4
    d_head = 64
    d_ff = 0
    n_experts = 16
    top_k = 2
    d_ff_expert = 256
    moe_every = 1
    capacity_factor = 1.25


class TestDerivePolicy:
    def test_default_equals_explicit_fixed(self):
        from repro.workloads import derive_workload
        base = derive_workload(TinyMoE(), "train_4k", n_gpus=16)
        fixed = derive_workload(TinyMoE(), "train_4k", n_gpus=16,
                                policy="fixed")
        assert [(c.collective, c.nbytes, c.label, c.buffer, c.stride)
                for c in base.calls] \
            == [(c.collective, c.nbytes, c.label, c.buffer, c.stride)
                for c in fixed.calls]

    def test_every_call_carries_provenance(self):
        from repro.workloads import derive_workload
        tr = derive_workload(TinyMoE(), "train_4k", n_gpus=16,
                             policy="fixed")
        assert all(c.logical and c.resolved_by for c in tr.calls)
        grads = [c for c in tr.calls if c.logical == "allreduce"]
        assert grads
        assert all(c.collective == "ring_allreduce"
                   and c.resolved_by == "fixed" for c in grads)

    def test_emitter_tracks_buffer_warmth(self):
        from repro.workloads import PodSpec
        from repro.workloads.derive import StepEmitter, resolve_pod
        pod = resolve_pod(PodSpec(n_gpus=8), TinyMoE(), "decode")
        em = StepEmitter(TinyMoE(), pod, policy=_diverging_table())
        em.emit("l0", "allreduce", 1 * MB, pod.n_gpus, 0.0, "grad", 0)
        em.emit("l1", "allreduce", 1 * MB, pod.n_gpus, 0.0, "grad", 0)
        em.mark_cold()
        em.emit("l2", "allreduce", 1 * MB, pod.n_gpus, 0.0, "grad", 0)
        assert [c.collective for c in em.calls] \
            == ["rd_allreduce", "ring_allreduce", "rd_allreduce"]
        assert [c.resolved_by for c in em.calls] \
            == ["table:cold", "table:warm", "table:cold"]


# ---------------------------------------------------------------- serving
class TinyServeMoE(TinyMoE):
    name = "tiny-serve-moe"


class TestServingPolicy:
    def test_fixed_policy_traffic_is_bit_for_bit(self):
        from repro.serving.simulate import TrafficPoint, _traffic_point
        base = dict(arch=TinyServeMoE(), rps=200.0, n_requests=4,
                    steps_cap=16, seed=3, prompt_mean=16, output_mean=3,
                    max_decode_slots=4, prefill_chunk_tokens=32)
        plain = _traffic_point((TrafficPoint(**base),))
        fixed = _traffic_point((TrafficPoint(policy="fixed", **base),))
        assert [s.comm_ns for s in plain.steps] \
            == [s.comm_ns for s in fixed.steps]
        assert plain.ttft_percentiles() == fixed.ttft_percentiles()


# --------------------------------------------------------------- fig (slow)
@pytest.mark.slow
def test_fig17_divergence_and_table_gain():
    """The fig17 acceptance criteria: at least one (collective, size,
    topology) point where the cold optimum differs from the warm optimum,
    and the table policy strictly beating the fixed default end-to-end
    through a policy-threaded session on that point."""
    import benchmarks.paper_figs as pf
    rows = {name: derived for (name, _val, derived)
            in pf.fig17_algorithm_selection()}
    assert "any=True" in rows["fig17/check_cold_warm_optima_diverge"]
    check = rows["fig17/check_table_beats_fixed_default"]
    assert "strict=True" in check
