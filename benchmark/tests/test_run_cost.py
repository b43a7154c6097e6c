"""What a run costs (PR 30): the ``[time]`` line's arithmetic, and the
replica's ``TRACE_STOP`` writing the ``.xplane.pb`` and nothing else."""
import types
from pathlib import Path

import replica_main
import run as bench_run
import trace_reduce


def test_stages_book_the_seconds_in_order_and_sum_to_the_total(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(bench_run.time, "monotonic", lambda: now[0])
    stages = bench_run.Stages(90.0)
    now[0] = 110.0
    stages.lap("children_up_s")
    now[0] = 200.0                      # the load ended at 200
    stages.lap("ramp_s", 115.0)         # instants that have passed
    stages.lap("window_s", 165.0)
    stages.lap("drain_s")
    stages.lap("late_s", 999.0)         # an instant that has not: now
    assert stages.seconds == {"children_up_s": 20.0, "ramp_s": 5.0,
                              "window_s": 50.0, "drain_s": 35.0,
                              "late_s": 0.0}
    stamp = lambda t: {"time_ns": 0, "monotonic": t}      # noqa: E731
    marks = {"trace_started": {"start": stamp(1.0), "running": stamp(1.25)},
             "trace_stopped": {"stop": stamp(9.0), "collected": stamp(12.0),
                               "written": stamp(12.5)}}
    spent = stages.report(marks, {"route": "proto"})
    assert spent["total_s"] == 110.0
    assert sum(v for k, v in spent.items()
               if k in stages.seconds) == spent["total_s"]
    assert (spent["trace_start_s"], spent["trace_collect_s"],
            spent["trace_stop_s"]) == (0.25, 3.0, 3.5)
    assert spent["reducer"] == {"route": "proto"}
    # an untraced run, and a replica that answered with an error
    assert "trace_stop_s" not in stages.report({}, {})
    assert "reducer" not in stages.report({}, {})
    assert "trace_stop_s" not in stages.report(
        {"trace_stopped": {"error": "RuntimeError: No profile started"}}, {})


def _short_trace(tmp_path, stop):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jax.jit(lambda x: (x @ x).sum())(jnp.ones((8, 8))).block_until_ready()
    return stop(str(tmp_path))


def test_trace_stop_writes_the_xplane_and_not_the_viewer_s_json(tmp_path):
    collected = _short_trace(tmp_path, replica_main._stop_trace)
    assert set(collected) == {"time_ns", "monotonic"}
    files = [p for p in tmp_path.rglob("*") if p.is_file()]
    assert [p.name.split(".", 1)[1] for p in files] == ["xplane.pb"]
    assert files[0].parent.parent == tmp_path / "plugins" / "profile"
    planes, _ = trace_reduce.load(str(files[0]))
    assert "/host:CPU" in [p["name"] for p in planes]
    # the session is closed: the next capture starts
    _short_trace(tmp_path / "again", replica_main._stop_trace)
    assert len(list(tmp_path.rglob("*.xplane.pb"))) == 2


def test_trace_stop_takes_the_public_call_where_jax_hides_its_session(
        tmp_path, monkeypatch):
    import jax
    from jax._src import profiler as impl
    calls = []
    monkeypatch.setattr(impl, "_profile_state", types.SimpleNamespace())
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop_trace"))
    assert set(replica_main._stop_trace(str(tmp_path))) \
        == {"time_ns", "monotonic"}
    assert calls == ["stop_trace"]
    assert not list(tmp_path.rglob("*"))


def test_no_reader_wants_the_file_that_is_no_longer_written():
    bench = Path(trace_reduce.__file__).parent
    held = [p.name for p in bench.rglob("*.py")
            if "tests" not in p.parts and p.name != "replica_main.py"
            and "json.gz" in p.read_text()]
    assert not held


def test_the_time_line_is_printed_before_the_result_and_kept_in_the_records():
    source = (Path(trace_reduce.__file__).parent / "run.py").read_text()
    at = source.index('say(f"[time] {json.dumps(spent)}")')
    assert source.index('"time": spent') > at
    assert source.index("print(json.dumps(result), flush=True)") > at
