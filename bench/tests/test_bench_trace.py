"""The trace reduction: busy and idle time, idle gaps named by the host
span around them, per-op and collective time, program runs inside a span;
on a hand-made trace with known answers and on a slice of a trace recorded
on the chip; kernels told apart on the Pallas calls of the real decode
program (``data/``)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devmetrics, trace_reduce  # noqa: E402

# times in microseconds: a chunk span [0, 100) holding a decode module
# [10, 90) with two ops, then a wait span [100, 200) with no device work,
# an admission span [200, 300) with a prefill module [210, 260)
TEXT = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 50000000
             stats { metadata_id: 1 str_value: "fusion.1" } }
    events { metadata_id: 2 offset_ps: 60000000 duration_ps: 30000000 }
    events { metadata_id: 3 offset_ps: 210000000 duration_ps: 50000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 80000000 }
    events { metadata_id: 5 offset_ps: 210000000 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.7" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.9" } }
  event_metadata { key: 4 value { id: 4 name: "jit_counted_decode" } }
  event_metadata { key: 5 value { id: 5 name: "jit_prefill_slot" } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_op" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 100000000 duration_ps: 100000000 }
    events { metadata_id: 3 offset_ps: 200000000 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.chunk" } }
  event_metadata { key: 2 value { id: 2 name: "bench.wait" } }
  event_metadata { key: 3 value { id: 3 name: "bench.admit" } } }
"""


@pytest.fixture
def made(tmp_path):
    from jax.profiler import ProfileData
    p = tmp_path / "t.xplane.pb"
    p.write_bytes(ProfileData.text_proto_to_serialized_xspace(TEXT))
    return trace_reduce.load(str(p))


def test_reduce_hand_made_trace(made):
    red = trace_reduce.reduce(made)
    assert red["window_s"] == pytest.approx(300e-6)
    assert red["busy_s"] == pytest.approx(130e-6)
    assert red["collective_s"] == pytest.approx(30e-6)
    assert red["ops"]["fusion.1"] == pytest.approx(50e-6)
    assert red["gap_total"]["bench.wait"] == pytest.approx(100e-6)
    assert red["gap_total"]["bench.chunk"] == pytest.approx(20e-6)
    assert red["gap_total"]["bench.admit"] == pytest.approx(50e-6)
    # the longest gap first: the wait, [100, 200)
    assert red["gaps"][0][0] == "bench.wait"


def test_program_runs_inside_spans(made):
    chunks = [s for s in made.spans if s.name == "bench.chunk"]
    t, runs = trace_reduce.module_time(made, lambda n: "decode" in n, chunks)
    assert t == pytest.approx(80e-6) and runs == 1
    mods = [m for m in made.devices[0].modules if "decode" in m.name]
    ops = trace_reduce.ops_in(made, mods, made.devices[0])
    assert [e.name for e in ops] == ["fusion.1", "all-reduce.7"]
    assert ops[0].stats["hlo_op"] == "fusion.1"


def test_kernels_are_told_apart_by_operand_shapes():
    hlo = (
        '  %f.1 = bf16[64,4608]{1,0} custom-call(%a, %b, %c), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{bf16[64,4096]{1,0}, bf16[4096,4608]{1,0}, bf16[1,4608]{1,0}}, '
        'backend_config={}\n'
        '  %f.2 = bf16[64,32,128]{2,1,0} custom-call(%q, %k, %v, %t, %l), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{bf16[64,32,128]{2,1,0}, bf16[3700,2,16,128]{3,2,1,0}, '
        'bf16[3700,2,16,128]{3,2,1,0}, s32[64,160]{1,0}, s32[64]{0}}, '
        'backend_config={}\n'
        '  %f.3 = bf16[64,4096]{1,0} custom-call(%x, %s), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{bf16[64,4096]{1,0}, f32[4096]{0}}, backend_config={}\n')
    k = devmetrics.kernels(hlo)
    assert k["f.1"] == ("gemm", [[64, 4096], [4096, 4608], [1, 4608]])
    assert k["f.2"][0] == "attn_decode_paged"
    assert k["f.3"][0] == "other"


def test_kernels_of_the_real_decode_program():
    """The Pallas calls of chatglm3-6b's decode chunk (capacity 64, pool
    3,700 pages), as the TPU compiler lays them out (AOT compile for a
    described v5e; the custom-call lines of the optimized HLO)."""
    hlo = (ROOT / "bench" / "tests" / "data"
           / "chatglm3-6b_decode_pallas_calls.hlo.txt").read_text()
    k = devmetrics.kernels(hlo)
    kinds = sorted(v[0] for v in k.values())
    assert kinds.count("gemm") == 8                # q k v o gate up down + head
    assert kinds.count("attn_decode_paged") == 1
    shapes = sorted(tuple(v[1][1]) for v in k.values() if v[0] == "gemm")
    assert shapes == sorted([(4096, 4096), (4096, 256), (4096, 256),
                             (4096, 4096), (4096, 13696), (4096, 13696),
                             (13696, 4096), (4096, 65024)])


def test_reduce_a_recorded_chip_trace(tmp_path):
    """54 ms of a traced chatglm3-6b.chat run on one v5e chip: the end of a
    decode chunk and the start of the admission after it, cut from the
    profiler's own trace (device ops and modules, the bench.* host spans)."""
    import gzip
    from jax.profiler import ProfileData
    text = gzip.decompress((ROOT / "bench" / "tests" / "data" /
                            "chatglm3-6b_chip_trace_slice.txtpb.gz")
                           .read_bytes()).decode()
    p = tmp_path / "chip.xplane.pb"
    p.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    tr = trace_reduce.load(str(p))
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    assert [s.name for s in tr.spans] == ["bench.chunk", "bench.admit"]
    # ops are named by their HLO instruction, not the whole HLO line
    assert all(" = " not in e.name for e in tr.devices[0].ops)
    red = trace_reduce.reduce(tr)
    assert red["window_s"] == pytest.approx(54e-3, rel=1e-6)
    assert red["busy_s"] == pytest.approx(37.25e-3, rel=1e-3)
    assert set(red["gap_total"]) == {"bench.chunk", "bench.admit",
                                     "host.other"}
    assert red["collective_s"] == 0.0
    # the decode program's ops, told apart by the real program's HLO
    chunk = [s for s in tr.spans if s.name == "bench.chunk"]
    t, runs = trace_reduce.module_time(tr, lambda n: "decode" in n, chunk)
    assert t == pytest.approx(35.12e-3, rel=1e-3) and runs == 1
    hlo = (ROOT / "bench" / "tests" / "data"
           / "chatglm3-6b_decode_pallas_calls.hlo.txt").read_text()
    k = devmetrics.kernels(hlo)
    mods = [m for m in tr.devices[0].modules if "decode" in m.name]
    kinds = [k[e.name][0] for e in trace_reduce.ops_in(tr, mods,
                                                       tr.devices[0])
             if e.name in k]
    assert kinds.count("gemm") == 26
    assert kinds.count("attn_decode_paged") == 4


def test_loop_ops_are_not_counted_over_their_body():
    ev = trace_reduce.Ev
    ops = [ev("while.1", 0, 100), ev("a", 10, 20), ev("b", 30, 90),
           ev("c", 40, 50), ev("d", 120, 130)]
    assert [e.name for e in trace_reduce.leaves(ops)] == ["a", "c", "d"]
