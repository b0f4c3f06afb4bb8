"""The reduction from a trace to intervals and metrics: a hand-made
trace with known arithmetic, and a small trace recorded on the chip."""
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
US = 1e-6


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    text = "\n".join(
        line for line in (DATA / "synthetic_trace.txt").read_text()
        .splitlines() if not line.startswith("#"))
    return tr.from_profile(ProfileData.from_text_proto(text))


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (3, 4), (9, 12)]) == \
        [(0, 1), (2, 3), (4, 9)]
    assert tr.subtract([(0, 1), (5, 6)], [(0.5, 5.5)]) == \
        [(0, 0.5), (5.5, 6)]
    assert tr.covered([(1, 2), (3, 4), (9, 12)], 1.5, 9.5) == 2.0
    assert tr.total([(0, 1), (2, 4)]) == 3


def test_busy_is_the_union_of_op_intervals_averaged_over_devices(synthetic):
    assert synthetic.devices == ["/device:TPU:0", "/device:TPU:1"]
    lo, hi = tr.window_of(synthetic)
    assert hi - lo == pytest.approx(11 * US)      # 0 .. 11 us
    # TPU:0 runs ops for 2 + 1 + 2 + 1 = 6 us, TPU:1 for 1 + 4 = 5 us
    assert tr.busy_seconds(synthetic) == pytest.approx(5.5 * US)


def test_a_named_ops_time_and_the_spans(synthetic):
    top = dict(tr.breakdown(synthetic)["device_ops"])
    assert top["fusion.1"] == pytest.approx(4 * US)
    assert top["all-gather.3"] == pytest.approx(1 * US)
    # a loop's container op spans its body's ops: not ranked beside them
    assert not any(name.startswith("while") for name in top)
    assert tr.span_seconds(synthetic, "decode_step") == \
        [pytest.approx(7 * US)]
    assert tr.module_seconds(synthetic, "decode") == [pytest.approx(6 * US)]


def test_breakdown_takes_the_ranking_made_from_the_name_stacks(synthetic):
    # run.py hands over xplane_scopes.top_ops: program, scope and the
    # end of tf_op beside the compiler's name; the idle gaps stay
    named = [[f"decode_fn/mlp/dot_general fusion.{i} bf16[8,128]", 12.0 - i]
             for i in range(12)]
    out = tr.breakdown(synthetic, device_ops=named)
    assert out["device_ops"] == named[:10]
    assert out["idle_gaps"] == tr.breakdown(synthetic)["idle_gaps"]
    # nothing to take (a trace with no name stacks): the compiler's names
    assert tr.breakdown(synthetic, device_ops=[])["device_ops"] == \
        tr.breakdown(synthetic)["device_ops"]


def test_gaps_go_to_the_innermost_host_span_under_way(synthetic):
    gaps = tr.idle_gaps(synthetic, min_gap=0.5 * US)
    named = [(round((b - a) / US, 3), name) for a, b, name in gaps]
    # [2,3) inside decode_step; [6,10): its middle (8 us) lies in
    # `sampling`, the innermost span open there
    assert named == [(1.0, "decode_step"), (4.0, "sampling")]
    out = dict(tr.breakdown(synthetic, min_gap=0.5 * US)["idle_gaps"])
    assert out["sampling"] == pytest.approx(4 * US)
    # short seams between ops are not attributed one by one
    seams = tr.idle_gaps(synthetic, min_gap=2 * US)
    assert [n for _, _, n in seams] == ["between_ops", "sampling"]


def test_exposed_collective_time(synthetic):
    # on each device its ops run one after another, so every second of
    # the all-gather is exposed: 1 us on TPU:0, 4 us on TPU:1. Both lie
    # inside a `while` event (the layers are one scan), which spans
    # them and hides nothing
    for ops in synthetic.ops.values():
        (ga, gb), = [(a, b) for a, b, n in ops if n.startswith("all-gather")]
        assert any(tr.CONTAINER_OP.match(n) and a <= ga and gb <= b
                   for a, b, n in ops)
    assert tr.exposed_collective_seconds(synthetic) == \
        pytest.approx(2.5 * US)


def test_recorded_chip_trace_reduces():
    """``data/tiny_tpu.xplane.pb``: recorded on a v5e by
    ``record_fixture.py`` — four runs of a toy program, each under a
    ``toy_step`` span, 2 ms of sleep between them."""
    trace = tr.reduce(str(DATA / "tiny_tpu.xplane.pb"))
    assert trace.devices == ["/device:TPU:0"]     # CUSTOM planes are not
    runs = tr.module_seconds(trace, "toy")
    assert len(runs) == 4
    assert all(r == pytest.approx(47.39e-6, rel=1e-3) for r in runs)
    assert len(tr.span_seconds(trace, "toy_step")) == 4
    lo, hi = tr.window_of(trace)
    busy = tr.busy_seconds(trace, lo, hi)
    assert busy == pytest.approx(sum(runs), rel=1e-3)
    assert 0 < busy < hi - lo
    top = tr.breakdown(trace)["device_ops"]
    assert top[0][0] == "fusion bf16[1024,1024]"   # name and shape kept
    assert top[0][1] == pytest.approx(50.5e-6, rel=1e-2)
    # the sleeps between runs show as idle gaps of a few milliseconds
    long_gaps = [g for g in tr.idle_gaps(trace) if g[1] - g[0] > 1e-3]
    assert len(long_gaps) >= 3
