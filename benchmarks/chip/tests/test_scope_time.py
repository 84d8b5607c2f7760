"""Device time by named scope (``benchmarks/chip/scopes.py``), its seven
readers and ``scope_report.py``, on hand-built HLO text and events, a
program compiled on the CPU and a small cell."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import scopes
from benchmarks.chip.run import HERE, load_file_module
from benchmarks.chip.tests import cases
from benchmarks.chip.xplane import Trace

NAMES = ("layer_scan", "attn", "moe.experts", "ep.dispatch", "ep.meta",
         "ep.combine", "ep.experts")

# A while body with a fusion whose own metadata has a scope, one whose
# root holds the scope, one whose tuple root is XLA's (the scope is on an
# operand), and the loop's carried-state copy that XLA inserted.
HLO = """HloModule jit_decode, is_scheduled=true

%fused_a (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="@/attn/mul"}
}

%fused_b (p0: f32[4]) -> (f32[4], f32[4]) {
  %p0 = f32[4]{0} parameter(0)
  %sub.2 = f32[4]{0} subtract(%p0, %p0), metadata={op_name="@/moe.experts/sub"}
  %convert.3 = f32[4]{0} convert(%sub.2)
  ROOT %tuple.4 = (f32[4]{0}, f32[4]{0}) tuple(%convert.3, %p0)
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %gte.0 = f32[4]{0} get-tuple-element(%arg), index=1
  %fusion.5 = f32[4]{0} fusion(%gte.0), calls=%fused_a, frontend_attributes={scope="a"}
  %fusion.6 = (f32[4]{0}, f32[4]{0}) fusion(%fusion.5), kind=kLoop, calls=%fused_b
  %ds_fusion.7 = f32[4]{0} fusion(%gte.0), calls=%fused_a, metadata={op_name="@/ds"}
  ROOT %tuple.8 = (s32[], f32[4]{0}) tuple(%gte.0, %fusion.5)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), frontend_attributes={kept="1",scope="x"}
  %while.10 = (s32[], f32[4]{0}) while(%x), body=%body, metadata={op_name="@"}
  %gte.11 = f32[4]{0} get-tuple-element(%while.10), index=1
  ROOT %copy.12 = f32[4]{0} copy(%gte.11)
}
""".replace("@", "jit(decode)/layer_scan/while/body")


def test_scope_map_picks_the_innermost_scope():
    m = scopes.scope_map(HLO, NAMES)
    assert m["%ds_fusion.7"] == "layer_scan"
    assert m["%while.10"] == "layer_scan"
    assert m["%x"] == scopes.UNSCOPED


def test_fusion_without_metadata_takes_its_roots_scope():
    m = scopes.scope_map(HLO, NAMES)
    assert m["%fusion.5"] == "attn"            # root has the scope
    assert m["%fusion.6"] == "moe.experts"     # XLA's tuple root: operand's
    assert m["%copy.12"] == scopes.UNSCOPED    # XLA's copy of the carry


def test_scope_map_of_a_compiled_program():
    def f(x, w):
        with jax.named_scope("layer_scan"):
            def body(c, wi):
                with jax.named_scope("attn"):
                    return jnp.tanh(c @ wi), None
            y, _ = jax.lax.scan(body, x, jnp.stack([w, w]))
        return y

    a = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    compiled = jax.jit(f).lower(a, a).compile()
    m = scopes.scope_map(compiled, NAMES)
    text = compiled.as_text()
    dots = [op for op in m if f"{op} = " in text
            and " dot(" in text.split(f"{op} = ", 1)[1].split("\n", 1)[0]]
    assert dots and {m[op] for op in dots} == {"attn"}
    assert "layer_scan" in m.values()


def test_strip_metadata_keeps_the_ops():
    got = scopes.strip_metadata(HLO)
    assert "metadata" not in got and "HloModule" not in got
    assert "scope=" not in got
    assert "%copy.12 = f32[4]{0} copy(%gte.11)" in got
    assert "%fusion.5 = f32[4]{0} fusion(%gte.0), calls=%fused_a\n" in got
    assert 'parameter(0), frontend_attributes={kept="1"}' in got


# Chip 0: two runs of the decode program and one of another program.
SMAP = {"%attn.1": "attn", "%exp.2": "moe.experts", "%ds.3": "layer_scan",
        "%copy.4": scopes.UNSCOPED}
DECODE_OPS = [("%attn.1 fusion bf16[4]", 0.0, 1.0),
              ("%exp.2 fusion bf16[4]", 1.0, 3.0),
              ("%ds.3 fusion bf16[4]", 3.0, 3.5),
              ("%copy.4 copy bf16[4]", 3.5, 4.0)]


def decode_trace():
    ops = DECODE_OPS + [(n, s + 10, e + 10) for n, s, e in DECODE_OPS]
    ops.append(("%attn.1 fusion bf16[4]", 20.5, 21.0))   # other program
    mods = [("jit_decode(3)", 0.0, 4.5), ("jit_decode(3)", 10.0, 14.5),
            ("jit_prefill(2)", 20.0, 22.0)]
    return Trace(ops={0: ops}, modules={0: mods})


def test_scope_time_drops_ops_outside_the_runs():
    got = scopes.scope_time(decode_trace(), 0, "jit_decode", SMAP, 0, 30)
    assert got == pytest.approx({"attn": 2.0, "moe.experts": 4.0,
                                 "layer_scan": 1.0, scopes.UNSCOPED: 1.0})
    one = scopes.scope_time(decode_trace(), 0, "jit_decode", SMAP, 5, 30)
    assert one["attn"] == pytest.approx(1.0)


def test_breakdown_adds_up_to_the_runs():
    got = scopes.breakdown(decode_trace(), 0, 0, 30,
                           {"decode": "jit_decode"}, {"jit_decode": SMAP})
    d = got["decode"]
    assert d["runs"] == 2 and d["run_ms"] == pytest.approx(4500.0)
    assert d["scopes"][0] == ["moe.experts", pytest.approx(2000.0)]
    assert sum(v for _, v in d["scopes"]) + d["idle_ms"] \
        == pytest.approx(d["run_ms"])


EP_SMAP = {"%a2a.1": "ep.dispatch", "%a2a.2": "ep.meta",
           "%a2a.3": "ep.combine", "%fusion.4": "ep.experts",
           "%fusion.5": "ep.dispatch"}
EP_OPS = [("%fusion.5 fusion bf16[4,8]", 0.0, 0.5),
          ("%a2a.1 all-to-all bf16[4,8]", 0.5, 1.5),
          ("%a2a.2 all-to-all s32[4,8]", 1.5, 1.6),
          ("%fusion.4 fusion bf16[4,8]", 1.6, 9.6),
          ("%a2a.3 all-to-all bf16[4,8]", 9.6, 10.6)]


def ep_trace():
    ops = EP_OPS + [(n, s + 20, e + 20) for n, s, e in EP_OPS]
    return Trace(ops={0: ops}, modules={0: [("jit_stack(1)", 0.0, 11.0),
                                            ("jit_stack(1)", 20.0, 31.0)]})


def reader(name):
    return load_file_module(HERE / "metrics" / f"{name}.py",
                            f"metric_{name.replace('.', '_')}")


READS = [
    ("attn_ms.decode", "decode", 1000.0),
    ("experts_ms.decode", "decode", 2000.0),
    ("layer_scan_ms.decode", "decode", 1000.0),
    ("a2a_dispatch_ms", "ep", 1000.0),
    ("a2a_meta_ms", "ep", 100.0),
    ("a2a_combine_ms", "ep", 1000.0),
    ("experts_ms.ep", "ep", 8000.0),
]


def reader_ctx(kind, records):
    tr = decode_trace() if kind == "decode" else ep_trace()
    return {"trace": tr, "window": (0.0, 40.0), "device": 0,
            "devices": [0], "records": records}


@pytest.mark.parametrize("name,kind,want", READS)
def test_readers_give_per_step_values(name, kind, want):
    if kind == "decode":
        rec = {"programs": {"prefill": "jit_prefill", "decode": "jit_decode"},
               "scopes": {"jit_decode": SMAP, "jit_prefill": {}}}
    else:
        rec = {"programs": {"step": "jit_stack"}, "steps": 2,
               "scopes": {"jit_stack": EP_SMAP}}
    got = reader(name).read(reader_ctx(kind, rec))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name,kind,want", READS)
def test_readers_give_none_without_their_records(name, kind, want):
    program, module = (("decode", "jit_decode") if kind == "decode"
                       else ("step", "jit_stack"))
    assert reader(name).read(reader_ctx(kind, {})) is None
    # The program ran, but without scopes (one older than them).
    bare = {"programs": {program: module}, "scopes": {module: {}}}
    assert reader(name).read(reader_ctx(kind, bare)) is None
    gone = {"programs": {program: "jit_other"}, "scopes": {"jit_other": SMAP}}
    assert reader(name).read(reader_ctx(kind, gone)) is None


@pytest.fixture
def no_compile_cache(monkeypatch):
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr("benchmarks.chip.run.enable_compile_cache",
                        lambda: None)


def test_report_counts_compiles_and_writes_programs(no_compile_cache,
                                                    tmp_path):
    from benchmarks.chip import scope_report

    res = scope_report.report(
        "granite_decode", cases.SEED, 0.3, False, out=tmp_path,
        devices=jax.devices()[:1], config=cases.granite_config(),
        traffic=cases.granite_traffic(cases.GRANITE_LIMIT))
    assert res["correct"], res["checks"]
    assert res["compiles"]["setup"]["compiles"] > 0
    assert res["compiles"]["window"] == {
        "traces": 0, "compiles": 0, "compile_s": 0.0, "cache_hits": 0}
    for key in ("prefill", "decode"):
        assert (tmp_path / f"{key}.hlo").read_text().startswith("HloModule")
        assert "metadata" not in (tmp_path / f"{key}.stripped.hlo").read_text()
