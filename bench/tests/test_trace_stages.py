"""Device time by stage: paths read from the programs a trace keeps, and
stage sums on hand-made six-field records and on the recorded chip slice."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce, trace_stages

DEV = "/device:TPU:0"
MS = 1_000_000


@pytest.mark.parametrize("path,program,stage", [
    ("jit(serve_step)/layers/while/body/closed_call/moe.experts/dot_general",
     "serve_step", "moe.experts"),
    ("jit(serve_step)/layers/while/body/dynamic_slice", "serve_step",
     "layers"),
    ("jit(serve_step)/layers/while/body/closed_call/attn.kv_write/squeeze;"
     "attn.qkv/reshape", "serve_step", "attn.kv_write"),
    ("jit(prefill_step)/moe.dispatch/jit(argsort)/sort", "prefill_step",
     "moe.dispatch"),
    ("jit(serve_step)", "serve_step", "(none)"),
    ("jit(serve_step)/while/body/dot_general", "serve_step", "(none)"),
])
def test_program_and_stage_of_a_path(path, program, stage):
    assert trace_stages.program_of(path) == program
    assert trace_stages.stage_of(path) == stage


def _records(devices=(DEV,)):
    """Two programs that share an op name, one op with no stage, a loop
    (left out: its ops are counted) and a five-field op (no path)."""
    out = []
    for dev in devices:
        out += [
            (dev, "XLA Modules", "jit_serve_step(3)", 0, 10 * MS),
            (dev, "XLA Modules", "jit_prefill_step(4)", 20 * MS, 10 * MS),
            (dev, "XLA Ops", "%while.1 = (...) while(...)", 1 * MS, 8 * MS,
             "jit(serve_step)/layers/while"),
            (dev, "XLA Ops", "%fusion.3 = bf16[8,64]{1,0} fusion(...)",
             1 * MS, 4 * MS,
             "jit(serve_step)/layers/while/body/closed_call/moe.experts/"
             "dot_general"),
            (dev, "XLA Ops", "%copy.7 = bf16[4,8]{1,0} copy(...)",
             5 * MS, 2 * MS, "jit(serve_step)"),
            (dev, "XLA Ops", "%dynamic-slice.2 = bf16[8]{0} ...",
             7 * MS, 1 * MS, "jit(serve_step)/layers/while/body/dynamic_slice"),
            (dev, "XLA Ops", "%fusion.3 = bf16[512,64]{1,0} fusion(...)",
             21 * MS, 6 * MS, "jit(prefill_step)/layers/while/body/"
             "closed_call/moe.experts/dot_general"),
            (dev, "XLA Ops", "%fusion.9 = f32[8]{0} fusion(...)",
             28 * MS, 1 * MS),
        ]
    return out


@pytest.mark.parametrize("n_dev", [1, 2], ids=["one-device", "two-devices"])
@pytest.mark.parametrize("n", [1, 2], ids=["one-slice", "two-slices"])
def test_stages_by_hand(n, n_dev):
    devices = [f"/device:TPU:{i}" for i in range(n_dev)]
    slices = [_records(devices)] * n
    # Devices are averaged and slices summed, as trace_reduce's ops are.
    assert trace_stages.stages(*slices) == {
        "serve_step": {"moe.experts": pytest.approx(0.004 * n),
                       "(none)": pytest.approx(0.002 * n),
                       "layers": pytest.approx(0.001 * n)},
        "prefill_step": {"moe.experts": pytest.approx(0.006 * n)},
    }
    ops = dict((k, t) for k, t in trace_stages.ops(*slices))
    # The same op name in two programs stays two entries, stage first.
    assert ops["prefill_step:moe.experts %fusion.3 = bf16[512,64] fusion(...)"] \
        == pytest.approx(0.006 * n)
    assert ops["serve_step:moe.experts %fusion.3 = bf16[8,64] fusion(...)"] \
        == pytest.approx(0.004 * n)
    assert ops["serve_step:(none) %copy.7 = bf16[4,8] copy(...)"] \
        == pytest.approx(0.002 * n)
    assert not any("while" in k for k in ops)
    # trace_reduce reads the same records without their sixth field.
    red = trace_reduce.reduce(*[[r[:5] for r in ev] for ev in slices])
    assert red["programs"]["jit_serve_step"]["count"] == n * n_dev


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "recorded_prefill_slice.json")


def test_recorded_slice_has_no_stages_and_reduces_as_before():
    with open(RECORDED) as f:
        events = [tuple(e) for e in json.load(f)]
    assert trace_stages.stages(events) == {}
    assert trace_stages.ops(events) == []
    red = trace_reduce.reduce(events)
    assert red["window_s"] == pytest.approx(0.040)
    assert trace_reduce.program(red, "prefill_step")["count"] == 1
    # The run's trace is found by its launches, counted as reduce counts.
    assert trace_stages._same_launches([events, events], trace_reduce.reduce(
        events, events)["programs"])


def _octal(data: bytes) -> str:
    return "".join(f"\\{b:03o}" for b in data)


@pytest.fixture(scope="module")
def scoped_program():
    """A scoped program compiled here: its HLO proto (as the profiler keeps
    it) and the instruction name of its expert matmul."""
    def f(x, w):
        with jax.named_scope("moe.experts"):
            return jnp.tanh(x @ w) * 2.0

    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    compiled = jax.jit(f).lower(x, jax.ShapeDtypeStruct((16, 16),
                                                        jnp.float32)).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    proto = module.as_serialized_hlo_module_proto()
    hlo_proto = b"\x0a" + _varint(len(proto)) + proto     # HloProto field 1
    names = trace_stages._hlo_op_names(hlo_proto)
    scoped = [n for n, p in names.items() if "/moe.experts/" in p]
    assert scoped, names
    return hlo_proto, scoped[0]


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def test_load_reads_paths_from_the_programs_the_trace_keeps(tmp_path,
                                                            scoped_program):
    hlo_proto, instr = scoped_program
    text = f"""
planes {{
  id: 1 name: "{DEV}"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }}
    events {{ metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_f(77)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%{instr} = f32[8,16] x" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%copy.99 = f32[8,16] y" }} }}
}}
planes {{
  id: 2 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_f(77)"
    stats {{ metadata_id: 5 bytes_value: "{_octal(hlo_proto)}" }} }} }}
  stat_metadata {{ key: 5 value {{ id: 5 name: "Hlo Proto" }} }}
}}
"""
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(text)
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    records = trace_stages.load(str(tmp_path))
    ops = [r for r in records if r[1] == "XLA Ops"]
    assert [len(r) for r in ops] == [6, 6]
    assert trace_stages.stage_of(ops[0][5]) == "moe.experts"
    assert ops[1][5] == "jit(f)"          # no such instruction: the root
    assert trace_stages.stages(records) == {
        "f": {"moe.experts": pytest.approx(3e-6), "(none)": pytest.approx(1e-6)}}
    assert trace_reduce.reduce([r[:5] for r in records])["busy_s"] \
        == pytest.approx(4e-6)


def test_run_slices_finds_the_run_trace_by_its_reduction(tmp_path,
                                                         monkeypatch):
    recs = [_records(), _records()[:1] + _records()[2:4]]
    dirs = {"a": recs[0], "b": recs[1]}
    monkeypatch.setattr(trace_stages.common, "CACHE", str(tmp_path))
    for name in dirs:
        os.makedirs(tmp_path / "trace" / name / "0")
    monkeypatch.setattr(trace_stages, "load",
                        lambda p: dirs[os.path.basename(os.path.dirname(p))])
    monkeypatch.setattr(trace_stages, "_newest", lambda d: 0.0)
    for name, ev in dirs.items():
        ctx = {"trace": trace_reduce.reduce([r[:5] for r in ev])}
        assert trace_stages.run_slices(ctx) == [ev]
        assert trace_stages.of_run(ctx) == trace_stages.stages(ev)
    assert trace_stages.of_run({"trace": None}) == {}
