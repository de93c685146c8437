"""The readers of the program's own spans, counters and op names
(``progtrace`` and the five metrics that read it), on hand-made traces
with known answers: ``serve.chunk`` spans paired with ``ServeReport.chunks``
by id even out of order and with the first span missing, kernels found by
their XAIF scope, four devices averaged, and a program without any of it
read from the benchmark's own spans."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import progtrace, spec, stats, trace_reduce  # noqa: E402
from repro.serve.scheduler import ChunkRecord  # noqa: E402

STEPS = 2                    # decode steps a chunk
US = 1_000_000               # picoseconds a microsecond
# per chunk, microseconds: the decode program, and inside it the paged
# attention, the gemm and one other op; chunk 2 dispatched nothing
MODULE = {0: 400, 1: 500, 3: 700}
ATTN = {0: 200, 1: 220, 3: 260}
GEMM = {0: 100, 1: 110, 3: 130}
OTHER = 20
SCOPE = "jit(decode_chunk)/decode_chunk/while/body/closed_call"
PROGRAM_HLO = "".join(
    f'  %{name} = bf16[8]{{0}} {op}(%p), metadata={{op_name="{path}" '
    f'stack_frame_id=1}}\n' for name, op, path in [
        ("while.1", "while", "jit(decode_chunk)/decode_chunk/while"),
        ("attn.1", "custom-call",
         f"{SCOPE}/xaif.attn_decode_paged.pallas/paged_attention"),
        ("gemm.1", "custom-call", f"{SCOPE}/xaif.gemm.pallas/gemm"),
        ("fusion.1", "fusion", f"{SCOPE}/add")])
# the same program before it named its ops: Pallas calls told apart by
# their operand shapes
PARENT_HLO = (
    '  %attn.1 = bf16[8,4,16]{2,1,0} custom-call(%q, %k, %v, %t, %l), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{bf16[8,4,16]{2,1,0}, bf16[80,2,16,16]{3,2,1,0}, '
    'bf16[80,2,16,16]{3,2,1,0}, s32[8,10]{1,0}, s32[8]{0}}, '
    'metadata={op_name="jit(counted_decode)/while/body/pallas_call"}\n'
    '  %gemm.1 = bf16[8,64]{1,0} custom-call(%a, %b, %c), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{bf16[8,64]{1,0}, bf16[64,64]{1,0}, bf16[1,64]{1,0}}, '
    'metadata={op_name="jit(counted_decode)/while/body/pallas_call"}\n')


def _chunk_span(c):
    return (1000 * c, 1000 * c + 900)


def _text(devices: int, serve_chunks, program: bool, shift: int = 0) -> str:
    """A trace of chunks 0-3: device planes (the decode program of each
    chunk that ran one, its while loop and leaf ops, device ``d`` taking
    ``1 + d/50`` times as long) and a host plane (``bench.chunk`` spans of
    every chunk; ``serve.chunk`` spans of ``serve_chunks``, in that order),
    the host's clock ``shift`` us ahead of the devices'."""
    planes = []
    for d in range(devices):
        f = 1 + d / 50
        ops, mods = [], []
        for c in MODULE:
            t = _chunk_span(c)[0] + 100
            mods.append((4, t, f * MODULE[c]))
            ops.append((1, t, f * MODULE[c]))                 # while
            ops.append((2, t + 10, f * ATTN[c]))
            ops.append((3, t + 10 + f * ATTN[c], f * GEMM[c]))
            ops.append((5, t + 10 + f * (ATTN[c] + GEMM[c]), f * OTHER))
        ev = lambda xs: "".join(
            f"events {{ metadata_id: {m} offset_ps: {int(a * US)} "
            f"duration_ps: {int(b * US)} }}\n" for m, a, b in xs)
        meta = "".join(
            f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
            for k, n in [(1, "while.1"), (2, "attn.1"), (3, "gemm.1"),
                         (4, "jit_decode_chunk" if program
                          else "jit_counted_decode"), (5, "fusion.1")])
        planes.append(
            f'planes {{ id: {d + 1} name: "/device:TPU:{d}"\n'
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{ev(ops)}}}\n'
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0\n'
            f'{ev(mods)}}}\n{meta}}}\n')
    host = []
    for c in range(4):
        a, b = _chunk_span(c)
        a, b = a + shift, b + shift
        host.append(f"events {{ metadata_id: 1 offset_ps: {(a + 5) * US} "
                    f"duration_ps: {(b - a - 10) * US} }}\n")
    for c in serve_chunks:
        a, b = (t + shift for t in _chunk_span(c))
        host.append(f"events {{ metadata_id: 2 offset_ps: {a * US} "
                    f"duration_ps: {(b - a) * US} "
                    f"stats {{ metadata_id: 1 int64_value: {c} }} }}\n")
    planes.append(
        'planes { id: 99 name: "/host:CPU"\n'
        'lines { id: 1 name: "python3" timestamp_ns: 0\n'
        + "".join(host) + "}\n"
        'event_metadata { key: 1 value { id: 1 name: "bench.chunk" } }\n'
        'event_metadata { key: 2 value { id: 2 name: "serve.chunk" } }\n'
        'stat_metadata { key: 1 value { id: 1 name: "chunk" } } }\n')
    return "".join(planes)


def _records():
    """The stream's chunk records (serve clock, s); chunk 2 dispatched
    nothing; host self-times 10, 20, 900, 40 ms."""
    out = []
    for c, (host, wait) in enumerate([(0.010, 0.3), (0.020, 0.5),
                                      (0.900, 0.0), (0.040, 0.6)]):
        t0 = 10.0 + c
        disp = t0 + host / 2 if wait else t0 + host
        out.append(ChunkRecord(c, t0, disp, disp + wait, t0 + host + wait,
                               8, 64))
    return out


def _ctx(tmp_path, devices=1, serve_chunks=(0, 1, 2, 3), program=True,
         shift=0):
    from jax.profiler import ProfileData
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            _text(devices, serve_chunks, program, shift)))
    tr = trace_reduce.load(str(tmp_path / "t.xplane.pb"))
    rec = SimpleNamespace(trace_dir=str(tmp_path), traced=(0.0, 1.0),
                          chunks=[])
    report = SimpleNamespace(chunks=_records()) if program \
        else SimpleNamespace()
    return dict(trace=tr, recorder=rec, report=report,
                engine=SimpleNamespace(chunk=STEPS), window=(10.0, 14.0),
                hlo=PROGRAM_HLO if program else PARENT_HLO, requests=[])


def _expect(table, chunks, devices=1):
    f = sum(1 + d / 50 for d in range(devices)) / devices
    per = [f * table[c] * 1e-6 for c in chunks]
    return 1e3 * stats.median(per) / STEPS


CASES = {
    # (devices, serve.chunk spans in the trace's order, chunks paired,
    #  host clock ahead of the devices' by us: each program then starts
    #  before its chunk's span)
    "in_order": (1, (0, 1, 2, 3), (0, 1, 3), 0),
    "out_of_order_first_missing": (1, (3, 2, 1), (1, 3), 0),
    "four_devices": (4, (1, 0, 3, 2), (0, 1, 3), 0),
    "clock_offset": (1, (0, 1, 2, 3), (0, 1, 3), 150),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("metric,table", [
    ("decode_step_ms", MODULE), ("decode_attn_ms", ATTN),
    ("decode_gemm_ms", GEMM)])
def test_device_readers_pair_chunks_by_id(tmp_path, case, metric, table):
    devices, order, paired, shift = CASES[case]
    ctx = _ctx(tmp_path, devices, order, shift=shift)
    assert [r.index for r, _ in progtrace.chunk_pairs(ctx)] == list(paired)
    v = spec.metric_reader(metric, ROOT)(ctx)
    assert v == pytest.approx(_expect(table, paired, devices), rel=1e-6)


def test_kernel_time_is_a_part_of_the_step(tmp_path):
    ctx = _ctx(tmp_path)
    read = lambda m: spec.metric_reader(m, ROOT)(ctx)
    assert read("decode_attn_ms") + read("decode_gemm_ms") \
        <= read("decode_step_ms")


@pytest.mark.parametrize("metric,table", [
    ("decode_step_ms", MODULE), ("decode_attn_ms", ATTN),
    ("decode_gemm_ms", GEMM)])
def test_device_readers_of_a_program_without_names(tmp_path, metric, table):
    """No serve.* span, no chunk records, no XAIF scope: the benchmark's
    bench.chunk spans and the Pallas calls' operand shapes."""
    ctx = _ctx(tmp_path, serve_chunks=(), program=False)
    v = spec.metric_reader(metric, ROOT)(ctx)
    assert v == pytest.approx(_expect(table, (0, 1, 3)), rel=1e-6)


def test_chunk_host_ms(tmp_path):
    ctx = _ctx(tmp_path)
    read = spec.metric_reader("chunk_host_ms", ROOT)
    # window [10, 14): all four records, host 10, 20, 900, 40 ms
    assert read(ctx) == pytest.approx(20.0)
    ctx["window"] = (11.0, 14.0)
    assert read(ctx) == pytest.approx(40.0)
    # without records: each bench.chunk span less the device's busy time
    ctx = _ctx(tmp_path, serve_chunks=(), program=False)
    host = sorted([890 - MODULE.get(c, 0) for c in range(4)])
    assert read(ctx) == pytest.approx(host[1] * 1e-3)


def _req(arrival, admitted, wait=None):
    r = SimpleNamespace(arrival=arrival, t_admitted=admitted)
    if wait is not None:
        r.wait_decode_s = wait
    return r


def test_queue_decode_p95_ms():
    read = spec.metric_reader("queue_decode_p95_ms", ROOT)
    rec = SimpleNamespace(chunks=[(1.0, 2.0, 8, []), (3.0, 4.0, 8, [])])
    reqs = [_req(0.0, 0.5, 0.01 * i) for i in range(20)]
    ctx = dict(requests=reqs, recorder=rec)
    assert read(ctx) == pytest.approx(180.0)        # 19th of 20
    # a request never admitted waited forever
    ctx["requests"] = reqs[:4] + [_req(0.0, None)]
    assert read(ctx) == stats.INF
    # without the counter: the queue time's overlap with the chunks
    ctx["requests"] = [_req(1.5, 3.5), _req(0.0, 0.9), _req(2.5, 4.5)]
    assert read(ctx) == pytest.approx(1000.0)
