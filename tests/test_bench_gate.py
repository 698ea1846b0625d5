"""tools/bench_gate.py: the rolling-baseline perf gate — record parsing
(summary JSON, JSONL metric streams, and regex salvage of the truncated
BENCH_r*.json tails), the median+MAD noise band, direction inference, and
the exit-code contract: 0 on pass, 3 (the tools/ offender convention) on
an injected regression."""

import importlib.util
import json
import pathlib

import pytest

_TOOLS = pathlib.Path(__file__).resolve().parent.parent / "tools"
REPO = _TOOLS.parent


def _load():
    spec = importlib.util.spec_from_file_location(
        "bench_gate", _TOOLS / "bench_gate.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench_gate = _load()


# -- record parsing ----------------------------------------------------------


def test_extract_legs_from_summary_dict():
    text = json.dumps({
        "metric": "bench_summary", "value": 2.0,
        "legs": {"a_tokens_per_sec": {"value": 100.0, "unit": "u",
                                      "vs_baseline": 2.0},
                 "b_images_per_sec": {"value": 50.0}},
    })
    assert bench_gate.extract_legs(text) == {
        "a_tokens_per_sec": 100.0, "b_images_per_sec": 50.0}


def test_extract_legs_from_jsonl_metric_stream():
    text = (json.dumps({"metric": "leg_a", "value": 1.5, "unit": "u"})
            + "\n" + json.dumps({"metric": "leg_b", "value": 2.5})
            + "\nnot json at all\n")
    assert bench_gate.extract_legs(text) == {"leg_a": 1.5, "leg_b": 2.5}


def test_extract_legs_salvages_torn_round_file_tail():
    """BENCH_r*.json archives truncate stdout to the last ~2000 chars, so
    the compact-summary line is usually torn at the FRONT — json.loads
    refuses it, but the interior leg entries are regex-recoverable."""
    torn = ('ma-125M: RoPE glue text that got cut..."\n'
            '{"metric":"bench_summary_compact",...TORN...'
            '"gpt2_124m_tokens_per_sec_per_chip": {"value": 129115.2, '
            '"unit": "t", "vs_baseline": 2.58}, '
            '"vit_b16_train_images_per_sec_per_chip": {"value": 781.2, '
            '"vs_baseline": 1.1}, "failed_leg_groups": []}\n')
    round_file = json.dumps({"n": 5, "cmd": "python bench.py", "rc": 0,
                             "tail": torn})
    legs = bench_gate.extract_legs(round_file)
    assert legs == {"gpt2_124m_tokens_per_sec_per_chip": 129115.2,
                    "vit_b16_train_images_per_sec_per_chip": 781.2}


def test_extract_legs_from_committed_round_archives(tmp_path):
    """The seed command's actual inputs: the committed BENCH_SUMMARY.json
    must yield every leg, and a round archive — the driver's record of
    one run: the command, its exit code and the LAST ~2000 chars of its
    stdout — at least one salvaged leg, whether its tail starts mid-line
    (the torn summary) or holds whole metric lines."""
    summary = REPO / "BENCH_SUMMARY.json"
    legs = bench_gate.extract_legs(summary.read_text())
    assert len(legs) >= 14
    torn = ('FU / 0.60 (the width-climb bar)", "vs_baseline": 1.1728}, '
            '"t5_small_tokens_per_sec_per_chip": {"value": 318621.02, '
            '"unit": "total (enc+dec) tokens/sec/chip", '
            '"vs_baseline": 0.3118}, "failed_leg_groups": []}\n')
    whole = (json.dumps({"metric": "resnet50_train_images_per_sec_per_chip",
                         "value": 2547.03, "unit": "images/sec/chip",
                         "vs_baseline": 1.132}) + "\n")
    for n, tail in ((1, torn), (2, whole)):
        rf = tmp_path / f"BENCH_r0{n}.json"
        rf.write_text(json.dumps(
            {"n": n, "cmd": "python bench.py", "rc": 0, "tail": tail}
        ))
    for rf in sorted(tmp_path.glob("BENCH_r0*.json")):
        assert bench_gate.extract_legs(rf.read_text()), rf.name


# -- direction + band --------------------------------------------------------


def test_lower_is_better_inference():
    lower = bench_gate.lower_is_better
    assert lower("gpt2_124m_anatomy_overhead_pct")
    assert lower("gpt2_124m_trace_overhead_pct")
    assert lower("preempt_recovery_s")
    assert lower("grad_sync_bytes_per_step")
    assert lower("serve_p99_latency_ms")
    # throughput names — including the _sec token — stay higher-is-better
    assert not lower("gpt2_124m_tokens_per_sec_per_chip")
    assert not lower("resnet50_train_images_per_sec_per_chip")
    assert not lower("gpt2_124m_decode_tokens_per_sec")


def test_baseline_band_widens_with_noise():
    med, band = bench_gate.baseline_of([100.0, 100.0, 100.0, 100.0])
    assert med == 100.0 and band == bench_gate.DEFAULT_BAND  # quiet: floor
    med, band = bench_gate.baseline_of([100.0, 90.0, 110.0, 80.0, 120.0])
    assert med == 100.0 and band == pytest.approx(0.30)  # 3*MAD/median


def test_judge_statuses():
    hist = [100.0] * 5
    assert bench_gate.judge("leg_tok_per_sec", 99.0, hist)["status"] \
        == "pass"
    bad = bench_gate.judge("leg_tok_per_sec", 90.0, hist)
    assert bad["status"] == "regression"
    assert bad["delta_pct"] == pytest.approx(-10.0)
    # lower-is-better: an INCREASE regresses
    assert bench_gate.judge("x_overhead_pct", 90.0, [100.0] * 5)["status"] \
        == "pass"
    assert bench_gate.judge("x_overhead_pct", 110.0, [100.0] * 5)["status"] \
        == "regression"
    # legs without enough history pass with a note, never fail
    assert bench_gate.judge("new_leg", 1.0, [])["status"] == "no-history"
    assert bench_gate.judge("new_leg", 1.0, [5.0])["status"] == "no-history"


# -- end-to-end: seed, pass, exit-3 on injected regression -------------------


def _summary_file(tmp_path, name, scale=1.0):
    legs = {"gpt2_tokens_per_sec": 100000.0 * scale,
            "anatomy_overhead_pct": 0.5 / scale}
    path = tmp_path / name
    path.write_text(json.dumps({
        "metric": "bench_summary", "value": 2.0,
        "legs": {k: {"value": v, "unit": "u", "vs_baseline": 1.0}
                 for k, v in legs.items()},
    }))
    return path


def test_gate_passes_history_and_fails_injected_regression(
        tmp_path, capsys):
    store = tmp_path / "store.json"
    history = [_summary_file(tmp_path, f"r{i}.json", scale=s)
               for i, s in enumerate([1.0, 1.01, 0.99, 1.0])]
    rc = bench_gate.main(["seed", "--store", str(store)]
                         + [str(p) for p in history])
    assert rc == 0
    assert len(json.loads(store.read_text())["gpt2_tokens_per_sec"]) == 4

    # a fresh record inside the noise band: exit 0
    fresh = _summary_file(tmp_path, "fresh.json", scale=1.005)
    assert bench_gate.main(["check", "--store", str(store),
                            str(fresh)]) == 0
    out = capsys.readouterr().out
    assert "within the noise band" in out

    # an injected 10% regression on BOTH directions: exit 3
    bad = _summary_file(tmp_path, "bad.json", scale=0.90)
    rc = bench_gate.main(["check", "--store", str(store), str(bad)])
    out = capsys.readouterr().out
    assert rc == 3  # the marker_audit/schema_audit offender convention
    assert "REGRESSION" in out
    # throughput fell AND the lower-is-better overhead leg rose
    assert out.count("REGRESSION") == 2


def test_gate_update_rolls_baseline_forward_only_on_pass(tmp_path):
    store = tmp_path / "store.json"
    for i in range(3):
        bench_gate.main(["seed", "--store", str(store),
                         str(_summary_file(tmp_path, f"r{i}.json"))])
    fresh = _summary_file(tmp_path, "fresh.json", scale=1.01)
    assert bench_gate.main(["check", "--store", str(store), "--update",
                            str(fresh)]) == 0
    assert len(json.loads(store.read_text())["gpt2_tokens_per_sec"]) == 4
    bad = _summary_file(tmp_path, "bad.json", scale=0.5)
    assert bench_gate.main(["check", "--store", str(store), "--update",
                            str(bad)]) == 3
    # the regressed values did NOT poison the store
    assert len(json.loads(store.read_text())["gpt2_tokens_per_sec"]) == 4


def test_gate_no_history_passes_with_note(tmp_path, capsys):
    store = tmp_path / "store.json"
    fresh = _summary_file(tmp_path, "fresh.json")
    assert bench_gate.main(["check", "--store", str(store),
                            str(fresh)]) == 0
    assert "no baseline yet" in capsys.readouterr().out


def test_gate_unreadable_record_exits_2(tmp_path):
    assert bench_gate.main(["check", "--store",
                            str(tmp_path / "s.json"),
                            str(tmp_path / "missing.json")]) == 2


def test_store_history_is_capped(tmp_path):
    store = tmp_path / "store.json"
    files = [str(_summary_file(tmp_path, f"r{i}.json"))
             for i in range(25)]
    bench_gate.main(["seed", "--store", str(store), "--keep", "10"]
                    + files)
    assert len(json.loads(store.read_text())["gpt2_tokens_per_sec"]) == 10


def test_bench_wires_the_gate():
    """bench.py exposes --gate (off by default) and schedules the anatomy
    overhead leg — source-level, no device work."""
    src = (REPO / "bench.py").read_text()
    assert '"--gate"' in src
    assert "bench_gate.py" in src
    assert '"anatomy": (bench_anatomy_overhead' in src
    assert '"metric": "gpt2_124m_anatomy_overhead_pct"' in src
