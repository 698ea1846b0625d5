import pytest

from benchmarks import meter, window


def rows(times, first_step=1):
    return [(first_step + i, 1.0, t) for i, t in enumerate(times)]


def test_rate_and_p90_over_all_steps_of_the_window():
    win = window.Window(warmup_steps=3, seconds=1.0)
    # steps 1..3 warm up; the window opens at step 3's resolve (t=10.0)
    times = [1.0, 5.0, 10.0] + [10.0 + 0.1 * k for k in range(1, 11)]
    times[-1] = 11.5  # one stall: the last interval is 0.6 s, not 0.1
    win.opened_at = 10.0
    m = window.window_metrics(rows(times), win, tokens_per_step=100, chips=2)
    assert m["steps"] == 10
    assert m["window_s"] == pytest.approx(1.5)
    assert m["tokens_per_s_per_chip"] == pytest.approx(10 * 100 / 1.5 / 2)
    # nine intervals of 100 ms and one of 600: the stall is in the tail
    assert m["step_ms_p90"] == pytest.approx(150.0)
    assert m["step_ms_max"] == pytest.approx(600.0)
    assert m["step_ms_median"] == pytest.approx(100.0)


def test_window_that_never_opened_is_an_error():
    win = window.Window(warmup_steps=5, seconds=1.0)
    with pytest.raises(RuntimeError, match="never opened"):
        window.window_metrics(rows([1.0, 2.0]), win, tokens_per_step=1,
                              chips=1)


def test_recorder_opens_the_window_at_the_last_warmup_resolve():
    pytest.importorskip("tpudist.metrics")
    win = window.Window(warmup_steps=2, seconds=0.0)
    seen = []
    rec = window.make_recorder(win, on_step=lambda s, t: seen.append(s))
    with rec:
        rec.start_timer()
        rec.log_memory({"bytes_in_use": 1})
        rec.log_step(1, 3.0, 0.1)
        assert win.opened_at is None
        rec.log_step(2, 2.0, 0.1)
        assert win.opened_at == rec.rows[1][2]
        rec.print_progress(0, 0, 1.0)
    assert seen == [1, 2] and rec.log_every == 5


def test_loader_ends_the_stream_when_the_window_has_lasted():
    win = window.Window(warmup_steps=2, seconds=0.0)
    make = lambda rng: lambda: {"tokens": rng.integers(0, 9, (4, 3))}
    loader = window.WindowLoader(make, 5, win, keep_first=2)
    assert loader.batch_size == 4 and not hasattr(loader, "__len__")
    it = iter(loader)
    first = [next(it) for _ in range(3)]  # warm-up + one: window not open
    win.opened_at = 0.0                   # opened long ago: seconds are up
    assert list(it) == []
    assert len(loader.first_batches) == 2
    again = window.WindowLoader(make, 5, window.Window(2, 0.0))
    assert (next(iter(again))["tokens"] == first[0]["tokens"]).all()


def test_union_of_nested_compile_events_and_in_window_count():
    assert meter.union_seconds([(0, 10), (2, 5), (9, 12)]) == 12
    assert meter.union_seconds([(0, 10), (20, 30)], since=5, until=25) == 10
    m = meter.CompileMeter.__new__(meter.CompileMeter)
    m.backend_compiles = [1.0, 7.5]
    assert m.compiles_between(5.0, 9.0) == 1  # the run is refused for it
    assert m.compiles_between(2.0, 7.0) == 0
