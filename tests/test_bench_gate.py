"""CI perf-regression gate (benchmarks/check_regression.py): pure
comparison logic — parsing, thresholds, normalization, width gating, and
the injected-slowdown self-test the perf-gate CI job runs."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.check_regression import (  # noqa: E402
    Row,
    compare,
    parse_rows,
    trend_csv,
)

BASELINE = """\
name,us_per_call,derived
sched.roundrobin.2t,476.52,launches_per_s=2099
sched.batched.2t,241.05,launches_per_s=4149;mean_width=2.0;speedup=1.98x
sched.modulo.batched.2t,250.00,launches_per_s=4000;mean_width=2.0
"""


def fresh_like(scale=1.0, width=2.0):
    return parse_rows(
        "name,us_per_call,derived\n"
        f"sched.roundrobin.2t,{476.52 * scale:.2f},launches_per_s=1\n"
        f"sched.batched.2t,{241.05 * scale:.2f},mean_width={width}\n"
        f"sched.modulo.batched.2t,{250.0 * scale:.2f},mean_width={width}\n")


def test_parse_rows_roundtrip():
    rows = parse_rows(BASELINE)
    assert set(rows) == {"sched.roundrobin.2t", "sched.batched.2t",
                         "sched.modulo.batched.2t"}
    r = rows["sched.batched.2t"]
    assert r.us_per_call == pytest.approx(241.05)
    assert r.mean_width == 2.0
    assert r.derived["speedup"] == "1.98x"
    assert rows["sched.roundrobin.2t"].mean_width is None


def test_gate_passes_identical_and_faster():
    base = parse_rows(BASELINE)
    assert compare(base, fresh_like(1.0)) == []
    assert compare(base, fresh_like(0.5)) == []       # faster is fine
    assert compare(base, fresh_like(1.2)) == []       # within 25%


def test_gate_fails_on_2x_slowdown():
    """The perf-gate CI job's self-test: --inject-slowdown 2 must fire."""
    base = parse_rows(BASELINE)
    failures = compare(base, fresh_like(2.0))
    assert len(failures) == 3
    assert all("us_per_call regressed" in f for f in failures)


def test_gate_fails_on_mean_width_drop():
    base = parse_rows(BASELINE)
    failures = compare(base, fresh_like(1.0, width=1.0))
    assert len(failures) == 2
    assert all("fusion regression" in f for f in failures)
    # rounding jitter is not a regression
    assert compare(base, fresh_like(1.0, width=1.96)) == []


def test_gate_normalization_absorbs_runner_speed():
    """A uniformly 3x slower runner passes when normalized by the
    round-robin reference row; a *relative* regression still fails."""
    base = parse_rows(BASELINE)
    slow_runner = fresh_like(3.0)
    assert compare(base, slow_runner) != []           # absolute gate fires
    assert compare(base, slow_runner,
                   normalize="sched.roundrobin.2t") == []
    # batched path alone regresses 2x on the same runner -> caught
    skewed = fresh_like(1.0)
    skewed["sched.batched.2t"].us_per_call *= 2
    assert any("sched.batched.2t" in f for f in
               compare(base, skewed, normalize="sched.roundrobin.2t"))


def test_gate_fails_on_disjoint_rows_and_bad_reference():
    base = parse_rows(BASELINE)
    assert compare(base, {}) != []
    other = {"unrelated": Row("unrelated", 1.0, {})}
    assert any("no common rows" in f for f in compare(base, other))
    assert any("missing" in f for f in
               compare(base, fresh_like(1.0), normalize="nope"))


def test_gate_flags_error_rows():
    base = parse_rows("name,us_per_call,derived\nsched.ERROR,0,boom\n")
    fresh = parse_rows("name,us_per_call,derived\nsched.ERROR,0,boom\n")
    assert any("unusable baseline" in f for f in compare(base, fresh))


GATED = """\
name,us_per_call,derived
sched.roundrobin.2t,100.00,launches_per_s=1
fault.detect_latency,2.00,state=quarantined;gate=abs
fault.cotenant.ratio,1.100,within_10pct=True;gate=skip
"""


def test_gate_skip_rows_never_fire():
    """gate=skip rows (higher-is-better ratios) are excluded from the
    us_per_call comparison entirely."""
    base = parse_rows(GATED)
    fresh = parse_rows(GATED.replace("1.100", "9.900"))
    assert compare(base, fresh, normalize="sched.roundrobin.2t") == []


def test_gate_abs_rows_compare_unnormalized():
    """gate=abs rows (deterministic counts — the fault-detection latency)
    ignore the runner-speed normalization: a slow runner never fires
    them, a real latency increase always does."""
    base = parse_rows(GATED)
    # uniformly 3x slower runner: normalized rows absorb it, the abs row
    # is a count and did not change -> pass
    slow = parse_rows(GATED.replace("100.00", "300.00"))
    assert compare(base, slow, normalize="sched.roundrobin.2t") == []
    # latency count doubled on an otherwise identical runner -> fail,
    # even though normalization would have (wrongly) scaled it away if
    # the reference row had also slowed
    worse = parse_rows(GATED.replace("2.00", "4.00")
                            .replace("100.00", "300.00"))
    fails = compare(base, worse, normalize="sched.roundrobin.2t")
    assert any("fault.detect_latency" in f for f in fails)


def test_gate_median_normalization_absorbs_runner_speed():
    """--normalize median: a uniformly slower runner cancels via the
    median fresh/baseline ratio (no single trusted reference row); a
    subset regression still fires because the bulk anchors the median."""
    base = parse_rows(BASELINE)
    assert compare(base, fresh_like(3.0), normalize="median") == []
    skewed = fresh_like(1.0)
    skewed["sched.batched.2t"].us_per_call *= 2
    fails = compare(base, skewed, normalize="median")
    assert any("sched.batched.2t" in f for f in fails)
    # every row is gated under median mode (no spared reference row)
    all_slow = fresh_like(1.0)
    all_slow["sched.roundrobin.2t"].us_per_call *= 2
    assert any("sched.roundrobin.2t" in f
               for f in compare(base, all_slow, normalize="median"))


def test_gate_median_ignores_flagged_rows():
    """gate=skip/abs rows stay out of the median (a huge ratio row must
    not drag the common-mode estimate)."""
    from benchmarks.check_regression import median_ratio

    base = parse_rows(GATED)
    fresh = parse_rows(GATED.replace("1.100", "99.0"))
    assert median_ratio(base, fresh) == pytest.approx(1.0)


def test_trend_csv_reports_ratios():
    base = parse_rows(BASELINE)
    fresh = fresh_like(2.0)
    text = trend_csv(base, fresh, normalize="sched.roundrobin.2t")
    lines = text.strip().splitlines()
    assert lines[0] == "name,baseline_us,fresh_us,ratio,normalized_ratio,gate"
    rows = {ln.split(",")[0]: ln.split(",") for ln in lines[1:]}
    assert set(rows) == {"sched.roundrobin.2t", "sched.batched.2t",
                         "sched.modulo.batched.2t"}
    # raw ratio 2.0, normalized ratio 1.0 (uniform slowdown cancels)
    assert float(rows["sched.batched.2t"][3]) == pytest.approx(2.0)
    assert float(rows["sched.batched.2t"][4]) == pytest.approx(1.0)
    # without a usable reference the normalized column is empty
    text2 = trend_csv(base, fresh, normalize=None)
    assert text2.splitlines()[1].split(",")[4] == ""


# ---------------------------------------------------------------------------
# Cross-push trend history aggregation (benchmarks/aggregate_trend.py)
# ---------------------------------------------------------------------------

TREND_A = """name,baseline_us,fresh_us,ratio,normalized_ratio,gate
sched.batched.2t,100.00,110.00,1.1000,1.0000,
mem.4_clients,0.30,0.30,1.0000,,abs
"""

TREND_B = TREND_A.replace("110.00", "220.00").replace("1.1000", "2.2000")


def test_history_fold_appends_and_labels():
    from benchmarks.aggregate_trend import HEADER, fold, parse_history

    h1 = fold("", TREND_A, "sha1")
    order, rows = parse_history(h1)
    assert h1.splitlines()[0] == HEADER
    assert order == ["sha1"] and len(rows["sha1"]) == 2
    assert rows["sha1"][0].startswith("sha1,sched.batched.2t,")
    h2 = fold(h1, TREND_B, "sha2")
    order, rows = parse_history(h2)
    assert order == ["sha1", "sha2"]
    assert "sha2,sched.batched.2t,100.00,220.00" in h2


def test_history_fold_idempotent_per_label_and_bounded():
    from benchmarks.aggregate_trend import fold, parse_history

    h = fold("", TREND_A, "sha1")
    h = fold(h, TREND_B, "sha1")       # CI retry: replaced, not doubled
    order, rows = parse_history(h)
    assert order == ["sha1"] and len(rows["sha1"]) == 2
    assert "220.00" in h and "110.00" not in h
    # bounded to the most recent `keep` pushes
    for i in range(5):
        h = fold(h, TREND_A, f"sha{i}", keep=3)
    order, _ = parse_history(h)
    assert order == ["sha2", "sha3", "sha4"]
    with pytest.raises(ValueError):
        fold("", TREND_A, "x", keep=0)


# ---------------------------------------------------------------------------
# Sparkline trend report (benchmarks/render_history.py)
# ---------------------------------------------------------------------------

HISTORY = """push,name,baseline_us,fresh_us,ratio,normalized_ratio,gate
sha1,sched.batched.2t,100.00,100.00,1.0000,1.0000,
sha1,mem.4_clients,0.30,0.30,1.0000,1.0000,abs
sha2,sched.batched.2t,100.00,150.00,1.5000,1.4000,
sha3,sched.batched.2t,100.00,120.00,1.2000,1.2000,
sha3,mem.4_clients,0.30,0.30,1.0000,1.0000,abs
garbage line without commas
sha3,bad.ratio,1.0,1.0,1.0,not_a_number,
"""


def test_render_history_parse_and_gaps():
    from benchmarks.render_history import parse_history as ph

    pushes, series = ph(HISTORY)
    assert pushes == ["sha1", "sha2", "sha3"]
    assert set(series) == {"sched.batched.2t", "mem.4_clients"}
    # mem row missing for sha2 -> gap in its series
    assert "sha2" not in series["mem.4_clients"]
    assert series["sched.batched.2t"]["sha2"] == pytest.approx(1.4)


def test_render_history_band_sparkline():
    from benchmarks.render_history import GAP, band_sparkline
    from repro.launch.dashboard import SPARK_CHARS

    s = band_sparkline([1.0, 1.4, None, 1.2])
    assert len(s) == 4 and s[2] == GAP
    assert s[0] == SPARK_CHARS[0]          # band min -> lowest glyph
    assert s[1] == SPARK_CHARS[-1]         # band max -> highest glyph
    # a flat series renders mid-band, not bottomed out
    flat = band_sparkline([1.0, 1.0, 1.0])
    assert flat == SPARK_CHARS[len(SPARK_CHARS) // 2] * 3
    assert band_sparkline([None, None]) == GAP * 2
    assert band_sparkline([]) == ""


def test_render_history_markdown_report():
    from benchmarks.render_history import render_markdown

    md = render_markdown(HISTORY)
    assert "| benchmark | trend |" in md
    assert "`sched.batched.2t`" in md and "`mem.4_clients`" in md
    row = next(ln for ln in md.splitlines() if "sched.batched.2t" in ln)
    # min 1.0, latest 1.2, max 1.4 from the normalized column
    assert "| 1.000 | 1.200 | 1.400 |" in row
    # empty history still renders a valid document
    assert "_(no rows yet)_" in render_markdown("")


def test_run_exits_nonzero_when_a_suite_raised(tmp_path, monkeypatch):
    """benchmarks.run keeps running the suites after one raises, writes
    every row, and then exits non-zero naming the suite that raised."""
    import types

    from benchmarks import run

    def ok(rows):
        rows.append("ok.row,1.00,")

    def boom(rows):
        raise RuntimeError("suite failed")

    for name, fn in (("_suite_boom", boom), ("_suite_ok", ok)):
        mod = types.ModuleType(name)
        mod.main = fn
        monkeypatch.setitem(sys.modules, name, mod)
    monkeypatch.setattr(run, "SUITES", {"boom": ("_suite_boom", ""),
                                        "ok": ("_suite_ok", "")})
    # a cache directory from the environment: the harness sets none
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    out = tmp_path / "bench.csv"
    monkeypatch.setattr(sys, "argv", ["run", "boom", "ok", "--out",
                                      str(out)])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "boom" in str(exc.value.code)
    rows = out.read_text().splitlines()
    assert rows[1].startswith("boom.ERROR,0,RuntimeError")
    assert rows[2] == "ok.row,1.00,"
