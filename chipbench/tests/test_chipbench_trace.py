"""The reduction from a trace to busy time, program time and labelled
idle gaps."""

from pathlib import Path

from chipbench import trace
from chipbench.spans import union_length

DATA = Path(__file__).with_name("data")


def test_union_counts_overlaps_once():
    assert union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4


def test_gaps_are_the_window_less_the_busy_union():
    busy = [(1, 2), (1.5, 3), (6, 7)]
    assert trace._gaps(busy, 0, 10) == [(0, 1), (3, 6), (7, 10)]


def test_gaps_take_the_label_of_the_span_that_covers_most_of_them():
    gaps = [(0, 10), (20, 30), (40, 50)]
    spans = [(0, 8, "pager.write"), (5, 30, "prefill"), (45, 46, "decode")]
    got = trace._label_gaps(gaps, spans)
    assert got == {"pager.write": 10e-9, "prefill": 10e-9, "decode": 10e-9}
    assert trace._label_gaps([(60, 70)], spans) == {trace.NO_SPAN: 10e-9}


def test_program_keys_drop_xla_suffixes():
    assert trace.program_key("jit__lambda(12)") == "jit__lambda"
    assert trace.program_key("jit_shuffle_histogram.3") == "jit_shuffle_histogram"
    assert trace.program_key("jit__lambda_") == "jit__lambda"


def test_reduction_of_a_recorded_v5e_trace():
    # three rounds of a 1024x1024 bf16 matmul sum (jit__lambda), a
    # tanh(x @ x.T) (jit_my_step) and the 300,000-key bucket histogram,
    # recorded on one TPU v5e
    s = trace.reduce(str(DATA / "v5e_small.xplane.pb"))
    assert s.devices == 1 and not s.dropped
    assert {k: len(v) for k, v in s.runs.items()} == {
        "jit__lambda": 3, "jit_my_step": 3, "jit_shuffle_histogram": 3}
    runs, secs = s.program("jit_shuffle_histogram")
    assert runs == 3 and abs(secs - 69.468e-6) < 1e-9
    kernel = s.op_seconds(r"^jit_shuffle_histogram/.*:tpu_custom_call$")
    assert abs(kernel - 62.481e-6) < 1e-9
    module_total = sum(r[2] for v in s.runs.values() for r in v)
    assert abs(s.busy_s - module_total) < 1e-6
    assert 0.98 < s.idle_share < 1.0
    assert s.breakdown(1)["device_ops"][0][0].endswith(":tpu_custom_call")
    assert abs(sum(s.idle_by_span.values()) - (s.window_s - s.busy_s)) < 1e-9


def _summary(runs, spans=(), dropped=False):
    return trace.Summary(window_s=1.0, busy_s=0.5, devices=1, dropped=dropped,
                         runs=runs, spans=list(spans))


class _Spans:
    def __init__(self, values):
        self.values = values


class _Ctx:
    def __init__(self, values):
        from chipbench import harness
        from chipbench.peaks import peaks

        self.spans = _Spans(values)
        self.peak = peaks("TPU v5 lite")
        self.cell = type("C", (), {"config": harness.load_json(
            harness.HERE / "configs" / "qwen2_5_3b.json")})


def test_prefill_share_covers_every_prefill_of_the_window():
    from chipbench import counts, harness

    reader = harness.load_module(harness.HERE / "metrics" / "prefill_roofline.py")
    ctx = _Ctx({"prefill_tokens": [128, 1024, 128]})
    ctx.spans.intervals = {"prefill": [(5.0, 5.5), (25.0, 27.0), (45.0, 45.5)]}
    least = sum(counts.roofline_seconds(*counts.prefill_cost(ctx.cell.config, t),
                                        ctx.peak) for t in (128, 1024, 128))
    assert abs(reader.read(None, ctx) - 100 * least / 3.0) < 1e-9
    assert 0 < reader.read(None, ctx) < 100
    ctx.spans.intervals = {}
    assert reader.read(None, ctx) is None


def test_decode_share_counts_the_traced_runs():
    from chipbench import counts, harness

    reader = harness.load_module(harness.HERE / "metrics" / "decode_step_roofline.py")
    ctx = _Ctx({"decode_positions": [200, 400]})
    out = type("O", (), {"trace": _summary({"jit__lambda": [("1", 0, 0.01)] * 4}),
                         "counters": {"programs": {"decode": "jit__lambda"}}})
    least = sum(counts.roofline_seconds(*counts.decode_cost(ctx.cell.config, n),
                                        ctx.peak) for n in (200, 400)) / 2
    assert abs(reader.read(out, ctx) - 100 * 4 * least / 0.04) < 1e-9
