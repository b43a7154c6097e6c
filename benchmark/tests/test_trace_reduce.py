"""trace_reduce on the small recorded trace (see make_small_xplane.py for
its events; every number below is worked by hand there), through both of
its routes, and on a generated trace for what reading costs."""
import builtins
import json
from pathlib import Path

import pytest

import make_small_xplane
import trace_reduce

PB = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module", params=trace_reduce.ROUTES)
def reduced(request):
    planes, route = trace_reduce.load(str(PB), request.param)
    assert route == request.param
    return trace_reduce.reduce(planes)


def test_only_device_planes_count(reduced):
    assert reduced["chips"] == 2
    assert reduced["window_s"] == pytest.approx(450e-6)


def test_busy_is_the_union_and_idle_the_worst_chip(reduced):
    assert reduced["busy_s_per_chip"] == pytest.approx([230e-6, 450e-6])
    assert reduced["busy_s"] == pytest.approx(340e-6)
    assert reduced["idle_pct_worst"] == pytest.approx(100 * (1 - 230 / 450))


def test_longest_gaps_first(reduced):
    gaps = reduced["longest_gaps"]
    assert [round(d * 1e6) for _, d in gaps] == [170, 50]
    assert gaps[0][0] == pytest.approx(1_000_000 + 230_000)   # ns, trace clock
    assert reduced["gap_count"] == 2


def test_op_ranking_uses_own_time_averaged_over_chips(reduced):
    own = dict(reduced["op_self_s"])
    # chip 0: while.1 100 - 30 - 40 = 30; fusion.1 30 + 30; chip 1: fusion.1 450
    assert own["fusion.1"] == pytest.approx((60e-6 + 450e-6) / 2)
    assert own["while.1"] == pytest.approx(30e-6 / 2)
    assert own["_paged_call.9"] == pytest.approx(40e-6 / 2)
    assert reduced["op_self_s"][0][0] == "fusion.1"
    assert reduced["op_self_total_s"] == pytest.approx(340e-6)


def test_kernel_and_collective_shares_read_the_ops_by_name(reduced):
    from layer_metrics import attn_kernel_busy_share_pct as attn
    from layer_metrics import collective_busy_share_pct as coll
    ctx = {"trace": reduced}
    assert attn.read(ctx) == pytest.approx(100 * 20 / 340)
    assert coll.read(ctx) == pytest.approx(100 * 25 / 340)
    assert attn.read({"trace": {}}) is None


def test_op_names_are_cut_at_the_equals_sign():
    line = "%_paged_call.9 = bf16[32,4,8,128]{3,2,1,0} custom-call(s32[32,256] %x)"
    assert trace_reduce.short_name(line) == "_paged_call.9"
    assert trace_reduce.short_name("jit_mixed_step(1)") == "jit_mixed_step(1)"


def test_module_executions_keep_their_name_without_fingerprint(reduced):
    durs = [d for _, d in reduced["modules"]["jit_mixed_step"]]
    assert durs == pytest.approx([230_000, 50_000])


def test_union_and_self_times_directly():
    assert trace_reduce.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    own = trace_reduce.self_times([("a", 0, 10), ("b", 1, 3), ("c", 2, 1),
                                   ("b", 6, 2)])
    assert own == {"a": 5, "b": 4, "c": 1}


# --- what reading costs, on a generated trace ---------------------------

@pytest.fixture(scope="module")
def generated(tmp_path_factory):
    """Two chips, 50 ops with HLO lines of 1,500 characters, 20
    executions; a host plane with ten times the device planes' events."""
    path = tmp_path_factory.mktemp("trace") / "generated.xplane.pb"
    return str(path), make_small_xplane.write_generated(str(path))


def test_both_routes_give_the_same_numbers_to_the_nanosecond(generated):
    """The generated instants and durations are not whole nanoseconds:
    both routes must cut the picoseconds as ProfileData does."""
    path, held = generated
    proto, fallback = (trace_reduce.reduce(trace_reduce.load(path, r)[0])
                       for r in trace_reduce.ROUTES)
    assert json.dumps(proto) == json.dumps(fallback)
    assert proto["chips"] == held["chips"]
    assert len(proto["modules"]["jit_mixed_step"]) == held["executions"]
    # chip 0's module events: the line's 5,000,000 ns + 0 ps and
    # + 209,035,300 ps, each 50 x 4,000,700 = 200,035,000 ps long
    assert proto["modules"]["jit_mixed_step"][:2] == [
        [5_000_000.0, 200_035.0], [5_209_035.0, 200_035.0]]
    # fusion.3's own time: 3,001,500 ps -> 3,001 ns a call
    assert dict(proto["op_self_s"])["fusion.3"] == pytest.approx(
        held["executions"] * 3001e-9)


def test_a_name_is_cut_once_per_distinct_op_and_skipped_lines_cost_no_event(
        generated, monkeypatch):
    """Count the calls, not the seconds: ``short_name`` runs once per
    entry of a device plane's event_metadata however many events name
    it, and only the two read lines of a device plane are decoded: the
    host plane's 20,000 events, ``Async XLA Ops``, ``Steps`` and a plane
    that is no TPU's are counted from their undecoded bytes."""
    path, held = generated
    cuts, decoded = [], []
    real_cut, real_decode = trace_reduce.short_name, trace_reduce._decode_line
    monkeypatch.setattr(trace_reduce, "short_name",
                        lambda n: cuts.append(n) or real_cut(n))

    def decode(raw):
        line = real_decode(raw)
        decoded.append((line.name, len(line.events)))
        return line

    monkeypatch.setattr(trace_reduce, "_decode_line", decode)
    planes, route = trace_reduce.load(path, "proto")
    assert route == "proto"
    assert len(cuts) == held["chips"] * held["distinct_ops"]
    assert len(cuts) < held["device_events"] / 10
    assert max(len(n) for n in cuts) > 1400          # the long HLO lines
    assert sorted(n for n, _ in decoded) \
        == sorted(trace_reduce.READ_LINES * held["chips"])
    assert sum(n for _, n in decoded) == held["device_events"]
    spent = trace_reduce.counts(planes)
    assert spent["events_read"] == held["device_events"]
    assert spent["skipped"]["/host:CPU"]["events"] == held["host_events"]
    assert spent["skipped"]["/device:TPU:0"]["largest"][0] \
        == ("Async XLA Ops", held["executions"])
    assert spent["skipped"]["/device:CUSTOM:Megascale"]["events"] == 1
    # every tuple of a line shares the name object of its op
    ops = next(ln for ln in planes[0]["lines"] if ln["name"] == "XLA Ops")
    assert len({id(e[0]) for e in ops["events"]}) == held["distinct_ops"] - 1


def test_the_page_of_text_names_what_was_not_read(generated):
    path, held = generated
    text = trace_reduce.inspect(trace_reduce.load(path, "proto")[0])
    assert f"line 'thread/0': {held['host_events'] // 4} events, not read" in text
    assert f"line 'Async XLA Ops': {held['executions']} events, not read" in text
    assert f"line 'XLA Ops': {held['device_events'] // 2 - held['executions']} events, " in text
    assert f"x{held['executions']:<7} fusion.3" in text
    fallback = trace_reduce.inspect(trace_reduce.load(path, "profile_data")[0])
    assert "line 'thread/0': ? events, not read" in fallback
    assert [ln for ln in text.splitlines() if "not read" not in ln] \
        == [ln for ln in fallback.splitlines() if "not read" not in ln]


def test_the_fallback_is_taken_and_announced_without_the_proto_package(
        monkeypatch, capsys, tmp_path):
    """``jax`` is the only package a traced run may need for its life."""
    real_import = builtins.__import__

    def no_protobuf(name, *args, **kwargs):
        if name.split(".")[:2] == ["google", "protobuf"]:
            raise ImportError(f"No module named {name!r} (made so by the test)")
        return real_import(name, *args, **kwargs)

    trace_reduce._messages.cache_clear()
    monkeypatch.setattr(builtins, "__import__", no_protobuf)
    planes, route = trace_reduce.load(str(PB))
    assert route == "profile_data"
    err = capsys.readouterr().err
    assert "google.protobuf cannot be imported" in err
    assert "jax.profiler.ProfileData" in err
    assert trace_reduce.reduce(planes)["busy_s"] == pytest.approx(340e-6)
    with pytest.raises(ImportError):
        trace_reduce.load(str(PB), "proto")
    # the command line takes the same way out and says what it took
    out = tmp_path / "reduced.json"
    assert trace_reduce.main([str(PB), str(out)]) == 0
    written = json.loads(out.read_text())
    assert written["reducer"]["route"] == "profile_data"
    assert written["reducer"]["events_read"] == 9
    assert written["chips"] == 2


def test_the_reduced_file_says_what_reducing_cost(tmp_path):
    out = tmp_path / "reduced.json"
    assert trace_reduce.main([str(PB), str(out)]) == 0
    written = json.loads(out.read_text())
    cost = written.pop("reducer")
    assert cost["route"] == "proto"
    assert set(cost) == {"route", "load_s", "reduce_s", "inspect_s",
                         "events_read", "skipped"}
    assert cost["events_read"] == 9
    assert cost["skipped"] == {"/host:CPU": {"lines": 1, "events": 1,
                                             "largest": [["python", 1]]}}
    # without that key it is what reduce gives, key for key
    planes, _ = trace_reduce.load(str(PB))
    assert written == json.loads(json.dumps(trace_reduce.reduce(planes)))
    assert "line 'python': 1 events, not read" \
        in Path(str(out) + ".inspect.txt").read_text()


def test_run_py_keeps_the_reducer_s_cost_out_of_what_metrics_read():
    """``run.py`` pops the key before any reader sees the trace."""
    source = (Path(trace_reduce.__file__).parent / "run.py").read_text()
    assert 'reduced.pop("reducer", {})' in source
    readers = Path(trace_reduce.__file__).parent / "layer_metrics"
    assert not [p.name for p in readers.glob("*.py")
                if '"reducer"' in p.read_text()]
