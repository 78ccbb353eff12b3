"""The reduction from a device trace to the per-layer numbers."""
import pytest

from bench import trace as tr
from bench.trace import Device, Event, Trace

TABLE = {"topk": ["topk_dense", "topk_streaming"]}


def _wave(t0, step_ns, n_steps, topk_ns, gap_ns=10):
    """Module events of one wave: n_steps step programs, then top-K."""
    out, t = [], t0
    for _ in range(n_steps):
        out.append(Event("jit_step(1)", t, step_ns))
        t += step_ns + gap_ns
    out.append(Event("jit_topk_dense(2)", t, topk_ns))
    return out, t + topk_ns


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_busy_is_clipped_to_the_window():
    ev = [Event("a", 0, 10), Event("b", 5, 10), Event("c", 30, 10)]
    assert tr.busy_ns(ev, 0, 100) == 25
    assert tr.busy_ns(ev, 8, 35) == 12


@pytest.mark.parametrize("hlo,short", [
    ("%fusion.1 = s32[1048576,16]{0,1:T(8,128)S(1)} fusion(s32[16]{0} %x),"
     " kind=kCustom, calls=%fused_computation.1", "%fusion.1 s32[1048576,16] kCustom"),
    ("%sort = (u32[16,200000]{1,0}, s32[16,200000]{1,0}) sort(u32[16] %b), "
     "dimensions={1}", "%sort u32[16,200000]"),
    ("plain-name", "plain-name"),
])
def test_op_name(hlo, short):
    assert tr.op_name(hlo) == short


def test_program_name_strips_jit_and_id():
    assert tr.program_name("jit_topk_dense(123)") == "topk_dense"
    assert tr.program_name("fusion") == "fusion"


def test_summarize_counts_waves_up_to_the_last_topk():
    w1, t1 = _wave(1_000, 100, 10, 7)
    w2, t = _wave(t1 + 500, 100, 10, 7)
    partial = [Event("jit_step(1)", t + 50, 100)]      # next wave, cut off
    mods = w1 + w2 + partial
    ops = [Event("%fusion.1 = s32[8,16]{0} fusion(x), kind=kCustom", e.start_ns,
                 e.dur_ns) for e in mods]
    host = [Event("PjitFunction(step)", t1 + 20, 400),
            Event("long wrapper", 0, 10**9)]
    s = tr.summarize(Trace({"/device:TPU:0": Device(mods, ops)}, host,
                           (0, t + 1000)), TABLE)
    assert s.waves == 2
    assert s.iteration_s == pytest.approx(2 * 10 * 100 / 1e9)
    assert s.topk_s == pytest.approx(2 * 7 / 1e9)
    assert s.busy_s == pytest.approx((2 * (10 * 100 + 7) + 100) / 1e9)
    assert s.window_s == pytest.approx((t + 1000) / 1e9)
    assert s.device_ops[0][0] == "step/%fusion.1 s32[8,16] kCustom"
    # the longest gap (between the waves, 500 ns) is named by the host
    # event that overlaps it, not by the wrapper around the whole run
    names = dict((round(v * 1e9), k) for k, v in s.idle_gaps)
    assert names[500] == "PjitFunction(step)"


def test_summarize_without_device_programs_is_none():
    assert tr.summarize(Trace({}, [], (0, 10)), TABLE) is None
    assert tr.summarize(Trace({"/device:TPU:0": Device([], [])}, [], (0, 10)),
                        TABLE) is None


def test_from_profile_reads_a_recorded_xspace():
    from jax.profiler import ProfileData

    text = """
    planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Modules" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
        events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 } }
      lines { id: 2 name: "XLA Ops" timestamp_ns: 0
        events { metadata_id: 3 offset_ps: 0 duration_ps: 5000000 }
        events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000 } }
      event_metadata { key: 1 value { id: 1 name: "jit_step(12)" } }
      event_metadata { key: 2 value { id: 2 name: "jit_topk_dense(3)" } }
      event_metadata { key: 3 value { id: 3 name: "%scatter = s32[4,16]{0} scatter(x)" } }
      event_metadata { key: 4 value { id: 4 name: "%sort = u32[16,4]{0} sort(x)" } }
    }
    planes { id: 2 name: "/host:CPU"
      lines { id: 1 name: "python3" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 5000000 duration_ps: 900000 } }
      event_metadata { key: 1 value { id: 1 name: "PjitFunction(topk_dense)" } }
    }
    """
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    s = tr.summarize(tr.from_profile(pd, (0, 10_000)), TABLE)
    assert s.waves == 1
    assert s.iteration_s == pytest.approx(5e-6)
    assert s.topk_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(6e-6)
    assert [k for k, _ in s.device_ops] == ["step/%scatter s32[4,16]",
                                            "topk_dense/%sort u32[16,4]"]
    assert ("PjitFunction(topk_dense)", pytest.approx(1e-6)) in s.idle_gaps


def test_program_table_names_the_topk_programs():
    assert set(tr.load_table()["topk"]) == {"topk_dense", "topk_streaming"}
