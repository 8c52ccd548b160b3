"""tools/telemetry_report.py over a fixture stream (ISSUE 1 satellite).

The report tool is the downstream consumer the JSONL schema_version
field exists for, so this tier-1 test pins: exact p50/p95 over a known
span distribution, cumulative-counter semantics (last flush value per
file), garbage-line tolerance, and the newer-schema warning.
"""

import importlib.util
import io
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "data", "telemetry_fixture.jsonl")


@pytest.fixture(scope="module")
def report():
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(REPO, "tools",
                                         "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_summarize_fixture(report):
    out = io.StringIO()
    records = report.load_records([FIXTURE], out=out)
    assert "unparseable line skipped" in out.getvalue()
    summ = report.summarize(records)
    assert summ["spans"]["step.bench"] == [0.1, 0.2, 0.3, 0.4, 0.5]
    # last cumulative flush wins, not the sum of flush records
    assert summ["counters"]["collectives.psum.calls"] == 5
    assert summ["gauges"]["amp.loss_scale"] == [65536.0, 32768.0]
    assert summ["events"]["amp.loss_scale_change"] == 1
    assert summ["unknown_schema"] == [99]


def test_print_report_table(report):
    out = io.StringIO()
    summ = report.summarize(report.load_records([FIXTURE], out=out))
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "step.bench" in text
    # p50 of [.1 .2 .3 .4 .5] is .3, p95 is .5 (nearest-rank)
    line = next(ln for ln in text.splitlines() if "step.bench" in ln)
    assert "0.3" in line and "0.5" in line
    assert "amp.loss_scale" in text
    assert "newer schema_version" in text and "99" in text


def test_multi_file_counter_aggregation(report, tmp_path):
    """Two ranks' files each contribute their own last cumulative
    total; the report sums across files."""
    a = tmp_path / "rank0.jsonl"
    b = tmp_path / "rank1.jsonl"
    a.write_text('{"schema_version":1,"t":1,"type":"counter",'
                 '"name":"c","value":3}\n')
    b.write_text('{"schema_version":1,"t":1,"type":"counter",'
                 '"name":"c","value":4}\n')
    summ = report.summarize(report.load_records([str(a), str(b)]))
    assert summ["counters"]["c"] == 7


def test_appended_runs_in_one_file_sum_counters(report, tmp_path):
    """The JSONL sink appends: two runs into one path each open with a
    meta record and restart counters at zero — the report must sum the
    per-run totals, not keep only the last run's."""
    f = tmp_path / "appended.jsonl"
    f.write_text(
        '{"schema_version":1,"t":1,"type":"meta","tags":{},"pid":1}\n'
        '{"schema_version":1,"t":2,"type":"counter","name":"c","value":3}\n'
        '{"schema_version":1,"t":3,"type":"meta","tags":{},"pid":2}\n'
        '{"schema_version":1,"t":4,"type":"counter","name":"c","value":2}\n'
        '{"schema_version":1,"t":5,"type":"counter","name":"c","value":4}\n')
    summ = report.summarize(report.load_records([str(f)]))
    # run 1 total 3 + run 2 last flush 4 (intermediate 2 superseded)
    assert summ["counters"]["c"] == 7


def test_main_exit_code(report, capsys):
    assert report.main([FIXTURE]) == 0
    assert "step.bench" in capsys.readouterr().out


def test_missing_schema_version_warns_once_best_effort(report, tmp_path):
    """ISSUE 4 satellite: a record with NO schema_version (hand-edited
    stream, pre-ISSUE-1 writer) is still summarized; one warning names
    the condition instead of silently dropping or crashing."""
    f = tmp_path / "old.jsonl"
    f.write_text(
        '{"t":1,"type":"gauge","name":"legacy.gauge","value":2.0}\n'
        '{"t":2,"type":"gauge","name":"legacy.gauge","value":4.0}\n'
        '{"schema_version":2,"t":3,"type":"gauge","name":"new.gauge",'
        '"value":1.0}\n')
    summ = report.summarize(report.load_records([str(f)]))
    assert summ["gauges"]["legacy.gauge"] == [2.0, 4.0]   # best-effort
    assert summ["missing_schema"] == 2
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert text.count("missing schema_version") == 1       # warn once
    assert "legacy.gauge" in text


def test_since_step_filters_stamped_records(report, tmp_path):
    """--since-step keeps step >= N records; unstamped records (meta,
    trace-time counters) pass through so run identity survives."""
    f = tmp_path / "steps.jsonl"
    f.write_text(
        '{"schema_version":2,"t":1,"type":"meta","tags":{},"pid":1}\n'
        '{"schema_version":2,"t":2,"step":5,"type":"gauge",'
        '"name":"train.loss","value":1.0}\n'
        '{"schema_version":2,"t":3,"step":9,"type":"gauge",'
        '"name":"train.loss","value":2.0}\n'
        '{"schema_version":2,"t":4,"step":10,"type":"gauge",'
        '"name":"train.loss","value":3.0}\n'
        '{"schema_version":2,"t":5,"type":"counter",'
        '"name":"collectives.psum.calls","value":7}\n')
    records = report.load_records([str(f)])
    kept = report.filter_since_step(records, 10)
    summ = report.summarize(kept)
    assert summ["gauges"]["train.loss"] == [3.0]
    assert summ["counters"]["collectives.psum.calls"] == 7  # unstamped
    # no filter = identity
    assert report.filter_since_step(records, None) is records


def test_ring_summary_derives_tp(report, tmp_path):
    """ISSUE 5 satellite: collectives.ring.* get a derived view — the
    per-call hop count implies the ring (tp) size, since every ring
    loop books exactly tp−1 hops."""
    f = tmp_path / "ring.jsonl"
    f.write_text(
        '{"schema_version":2,"t":1,"type":"counter",'
        '"name":"collectives.ring.calls","value":6}\n'
        '{"schema_version":2,"t":2,"type":"counter",'
        '"name":"collectives.ring.hops","value":42}\n'
        '{"schema_version":2,"t":3,"type":"counter",'
        '"name":"collectives.ring.bytes","value":4096}\n')
    summ = report.summarize(report.load_records([str(f)]))
    ring = report.ring_summary(summ["counters"])
    assert ring["calls"] == 6 and ring["hops"] == 42
    assert ring["hops_per_call"] == 7 and ring["tp"] == 8
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "ring collectives" in text
    assert "ring size (tp) 8" in text
    # no ring calls -> no derived section, not a crash
    assert report.ring_summary({"collectives.psum.calls": 3}) is None
    # mixed-tp streams: non-integral hops/call is flagged, not rounded
    mixed = report.ring_summary({"collectives.ring.calls": 2.0,
                                 "collectives.ring.hops": 3.0})
    assert mixed["tp"] is None


def test_serving_summary_fixture(report, tmp_path):
    """ISSUE 6 satellite: the paged serving gauges/counters get a
    derived view — block-pool high-water, preemption rate per admitted
    request, and the prefix-share ratio at the pool high-water."""
    f = tmp_path / "serving.jsonl"
    f.write_text(
        '{"schema_version":2,"t":1,"type":"gauge",'
        '"name":"serving.blocks_in_use","value":3}\n'
        '{"schema_version":2,"t":2,"type":"gauge",'
        '"name":"serving.blocks_in_use","value":10}\n'
        '{"schema_version":2,"t":3,"type":"gauge",'
        '"name":"serving.blocks_in_use","value":0}\n'
        '{"schema_version":2,"t":4,"type":"gauge",'
        '"name":"serving.prefix_shared_blocks","value":4}\n'
        '{"schema_version":2,"t":5,"type":"counter",'
        '"name":"serving.requests","value":8}\n'
        '{"schema_version":2,"t":6,"type":"counter",'
        '"name":"serving.preemptions","value":2}\n')
    summ = report.summarize(report.load_records([str(f)]))
    serving = report.serving_summary(summ)
    assert serving["blocks_high_water"] == 10
    assert serving["blocks_last"] == 0            # drained, no leak
    assert serving["preemption_rate"] == 0.25
    assert serving["prefix_shared_high_water"] == 4
    # unequal series lengths (truncated stream): upper-bound fallback
    assert serving["prefix_share_ratio"] == 0.4
    # the engine emits both gauges in lockstep — equal-length series
    # pair record-for-record, and the ratio is the shared count AT the
    # high-water instant, not the stream max (which can postdate it)
    paired = report.serving_summary({
        "gauges": {"serving.blocks_in_use": [3.0, 10.0, 5.0],
                   "serving.prefix_shared_blocks": [0.0, 2.0, 4.0]},
        "counters": {"serving.requests": 8.0}})
    assert paired["prefix_share_ratio"] == 0.2    # 2/10, not 4/10
    assert paired["prefix_shared_high_water"] == 4
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "paged serving" in text
    assert "block-pool high-water 10" in text
    assert "rate 0.25" in text
    assert "share ratio 0.4" in text
    # a contiguous-engine stream (no block gauges) -> no section
    assert report.serving_summary(
        {"gauges": {"serving.queue_depth": [1.0]},
         "counters": {"serving.requests": 3.0}}) is None


def test_since_step_cli_flag(report, tmp_path, capsys):
    f = tmp_path / "steps.jsonl"
    f.write_text(
        '{"schema_version":2,"t":2,"step":1,"type":"gauge",'
        '"name":"train.loss","value":1.0}\n'
        '{"schema_version":2,"t":3,"step":8,"type":"gauge",'
        '"name":"train.loss","value":99.0}\n')
    assert report.main(["--since-step", "5", str(f)]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if "train.loss" in ln)
    assert "99" in line and line.split()[1] == "1"   # count == 1


def test_spec_summary_fixture(report, tmp_path):
    """ISSUE 8 satellite: the speculative-decoding counters get a
    derived view — accept rate = accepted/draft and the verify-call
    amortization (emitted tokens per per-sequence verify pass)."""
    f = tmp_path / "spec.jsonl"
    f.write_text(
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"generate.spec.draft_tokens","value":80}\n'
        '{"schema_version":3,"t":2,"type":"counter",'
        '"name":"generate.spec.accepted_tokens","value":60}\n'
        '{"schema_version":3,"t":3,"type":"counter",'
        '"name":"generate.spec.verify_calls","value":10}\n')
    summ = report.summarize(report.load_records([str(f)]))
    spec = report.spec_summary(summ["counters"])
    assert spec["accept_rate"] == 0.75            # 60 / 80
    assert spec["tokens_per_verify"] == 7.0       # (60 + 10) / 10
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "speculative decoding" in text
    assert "accept rate 0.75" in text
    assert "tokens/verify 7" in text
    # a spec-off stream (no draft counter) -> no section
    assert report.spec_summary({"serving.requests": 3.0}) is None
    # verify counter missing entirely (wounded stream): rate still
    # reported, amortization honestly absent
    partial = report.spec_summary({
        "generate.spec.draft_tokens": 8.0,
        "generate.spec.accepted_tokens": 4.0})
    assert partial["accept_rate"] == 0.5
    assert partial["tokens_per_verify"] is None


def test_controller_summary_fixture(report, tmp_path):
    """ISSUE 15 satellite: the elastic-controller counters/gauges get
    a derived view — actions by kind+pool, drained requests,
    chip-seconds, final pool sizes — and an absent stream hides the
    section."""
    f = tmp_path / "ctrl.jsonl"
    f.write_text(
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"controller.actions","value":2,'
        '"tags":{"action":"spawn","pool":"decode"}}\n'
        '{"schema_version":3,"t":2,"type":"counter",'
        '"name":"controller.actions","value":1,'
        '"tags":{"action":"drain","pool":"decode"}}\n'
        '{"schema_version":3,"t":3,"type":"counter",'
        '"name":"controller.drained_requests","value":3}\n'
        '{"schema_version":3,"t":4,"type":"gauge",'
        '"name":"controller.chip_seconds","value":41.5}\n'
        '{"schema_version":3,"t":5,"type":"gauge",'
        '"name":"controller.pool_size","value":2,'
        '"tags":{"pool":"decode"}}\n'
        '{"schema_version":3,"t":6,"type":"gauge",'
        '"name":"controller.pool_size","value":1,'
        '"tags":{"pool":"prefill"}}\n')
    summ = report.summarize(report.load_records([str(f)]))
    ctrl = report.controller_summary(summ)
    assert ctrl["spawns"] == 2
    assert ctrl["drains"] == 1
    assert ctrl["drained_requests"] == 3
    assert ctrl["chip_seconds"] == 41.5
    assert ctrl["pool_size_last"] == {"decode": 2.0, "prefill": 1.0}
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "elastic pool controller" in text
    assert "spawns 2" in text and "drains 1" in text
    assert "chip-seconds 41.5" in text
    assert "decode:2" in text
    # a controller-free stream -> no section
    assert report.controller_summary(
        {"counters": {"serving.requests": 3.0}, "gauges": {}}) is None


# -- aggregate_telemetry --window (ISSUE 9 satellite) ------------------------


@pytest.fixture(scope="module")
def aggregate():
    spec = importlib.util.spec_from_file_location(
        "aggregate_telemetry", os.path.join(REPO, "tools",
                                            "aggregate_telemetry.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _segment(sketch_mod, name, values, counter):
    """One appended run segment: meta + a cumulative sketch flush + a
    cumulative counter flush."""
    import json

    sk = sketch_mod.LogBucketSketch()
    for v in values:
        sk.observe(v)
    return (
        json.dumps({"type": "meta", "schema_version": 3}) + "\n"
        + json.dumps({"type": "sketch", "name": name,
                      "tags": {"slo_class": "interactive"},
                      "value": sk.to_dict()}) + "\n"
        + json.dumps({"type": "counter",
                      "name": "serving.goodput.met",
                      "tags": {"slo_class": "interactive"},
                      "value": counter}) + "\n")


def test_window_merges_only_last_n_segments(aggregate, tmp_path):
    """--window N: an autoscaler polling recent fleet percentiles must
    not see lifetime history — only each file's last N run segments
    merge.  Lifetime (no window) still merges everything."""
    sketch_mod = aggregate.load_sketch_module()
    f = tmp_path / "host0.jsonl"
    f.write_text(
        _segment(sketch_mod, "serving.ttft_ms", [1.0] * 8, 8.0)
        + _segment(sketch_mod, "serving.ttft_ms", [100.0] * 4, 4.0)
        + _segment(sketch_mod, "serving.ttft_ms", [1000.0] * 2, 2.0))
    key = "serving.ttft_ms{slo_class=interactive}"
    records = aggregate.load_records([str(f)])

    lifetime = aggregate.aggregate(records)
    assert lifetime["sketches"][key]["count"] == 14
    assert lifetime["counters"][
        "serving.goodput.met{slo_class=interactive}"] == 14.0

    last1 = aggregate.aggregate(aggregate.windowed(records, 1))
    assert last1["sketches"][key]["count"] == 2
    assert last1["sketches"][key]["p50"] >= 1000.0 * 0.96
    assert last1["goodput"]["interactive"]["met"] == 2.0

    last2 = aggregate.aggregate(aggregate.windowed(records, 2))
    assert last2["sketches"][key]["count"] == 6
    # window wider than history = lifetime
    assert aggregate.aggregate(aggregate.windowed(records, 99))[
        "sketches"][key]["count"] == 14

    with pytest.raises(ValueError, match="window"):
        aggregate.windowed(records, 0)


def test_window_is_per_file(aggregate, tmp_path):
    """Each FILE keeps its own last-N segments (hosts flush on their
    own cadence; one busy host must not evict another's only
    segment)."""
    sketch_mod = aggregate.load_sketch_module()
    a = tmp_path / "a.jsonl"
    a.write_text(
        _segment(sketch_mod, "serving.ttft_ms", [1.0] * 4, 4.0)
        + _segment(sketch_mod, "serving.ttft_ms", [10.0] * 3, 3.0))
    b = tmp_path / "b.jsonl"
    b.write_text(_segment(sketch_mod, "serving.ttft_ms", [10.0] * 5,
                          5.0))
    agg = aggregate.aggregate(aggregate.windowed(
        aggregate.load_records([str(a), str(b)]), 1))
    key = "serving.ttft_ms{slo_class=interactive}"
    # a's last segment (3) + b's only segment (5)
    assert agg["sketches"][key]["count"] == 8
    assert agg["goodput"]["interactive"]["met"] == 8.0


def test_window_cli_flag(aggregate, tmp_path, capsys):
    sketch_mod = aggregate.load_sketch_module()
    f = tmp_path / "h.jsonl"
    f.write_text(
        _segment(sketch_mod, "serving.ttft_ms", [1.0] * 8, 8.0)
        + _segment(sketch_mod, "serving.ttft_ms", [5.0] * 2, 2.0))
    out_json = tmp_path / "agg.json"
    rc = aggregate.main(["--window", "1", "--json", str(out_json),
                         str(f)])
    assert rc == 0
    import json

    agg = json.loads(out_json.read_text())
    assert agg["window"] == 1
    assert agg["sketches"][
        "serving.ttft_ms{slo_class=interactive}"]["count"] == 2


def test_moe_summary_from_stream(report, tmp_path):
    """The ISSUE-10 MoE view: wire-vs-raw dispatch ratio, the
    hops == (ep-1) x calls ring check with the implied ep, and the
    expert-load imbalance from the bench-probe gauges."""
    f = tmp_path / "moe.jsonl"
    f.write_text(
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"moe.dispatch_bytes","value":72000}\n'
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"moe.dispatch_raw_bytes","value":256000}\n'
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"moe.ring_calls","value":6}\n'
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"moe.ring_hops","value":42}\n'
        '{"schema_version":3,"t":2,"type":"gauge",'
        '"name":"moe.expert_load_max","value":24}\n'
        '{"schema_version":3,"t":2,"type":"gauge",'
        '"name":"moe.expert_load_mean","value":16}\n')
    summ = report.summarize(report.load_records([str(f)]))
    moe = report.moe_summary(summ)
    assert moe is not None
    assert moe["wire_over_raw"] == pytest.approx(72000 / 256000)
    assert moe["hops_per_call"] == pytest.approx(7.0)
    assert moe["ep"] == 8                    # hops/call + 1
    assert moe["load_imbalance"] == pytest.approx(1.5)
    import io
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "expert-parallel MoE" in text
    assert "ep 8" in text
    assert "imbalance 1.5" in text


def test_moe_summary_absent_for_dense_streams(report, tmp_path):
    f = tmp_path / "dense.jsonl"
    f.write_text('{"schema_version":3,"t":1,"type":"counter",'
                 '"name":"collectives.ring.calls","value":2}\n')
    summ = report.summarize(report.load_records([str(f)]))
    assert report.moe_summary(summ) is None


def test_checkpoint_summary_from_stream(report, tmp_path):
    """The ISSUE-11 checkpoint view: save/restore ms p50/p95 from the
    span series, bytes + rollback counters, and the overlap-ratio
    gauge — plus the printed section with the rollback callout."""
    f = tmp_path / "ckpt.jsonl"
    lines = []
    for v in (0.10, 0.12, 0.14, 0.40):       # save seconds -> ms
        lines.append('{"schema_version":3,"t":1,"type":"span",'
                     f'"name":"checkpoint.save","value":{v}}}')
    lines.append('{"schema_version":3,"t":1,"type":"span",'
                 '"name":"checkpoint.blocking","value":0.002}')
    lines.append('{"schema_version":3,"t":2,"type":"span",'
                 '"name":"checkpoint.restore","value":0.25}')
    for name, v in (("checkpoint.saves", 4), ("checkpoint.bytes", 8192),
                    ("checkpoint.restores", 1),
                    ("checkpoint.rollbacks", 1)):
        lines.append('{"schema_version":3,"t":3,"type":"counter",'
                     f'"name":"{name}","value":{v}}}')
    lines.append('{"schema_version":3,"t":3,"type":"gauge",'
                 '"name":"checkpoint.overlap_ratio","value":0.996}')
    f.write_text("\n".join(lines) + "\n")
    summ = report.summarize(report.load_records([str(f)]))
    ck = report.checkpoint_summary(summ)
    assert ck is not None
    assert ck["saves"] == 4 and ck["bytes"] == 8192
    assert ck["rollbacks"] == 1 and ck["restores"] == 1
    # nearest-rank on 4 samples: p50 -> index round(1.5) = 2
    assert ck["save_ms"]["p50"] == pytest.approx(140.0)
    assert ck["save_ms"]["p95"] == pytest.approx(400.0)
    assert ck["restore_ms"]["p50"] == pytest.approx(250.0)
    assert ck["blocking_ms"]["p50"] == pytest.approx(2.0)
    assert ck["overlap_ratio"] == pytest.approx(0.996)
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "checkpointing (checkpoint.*)" in text
    assert "overlap ratio 0.996" in text
    assert "ROLLBACKS 1" in text
    assert "health_report" in text


def test_checkpoint_summary_absent_without_series(report, tmp_path):
    f = tmp_path / "nock.jsonl"
    f.write_text('{"schema_version":3,"t":1,"type":"counter",'
                 '"name":"train.overflow_count","value":2}\n')
    summ = report.summarize(report.load_records([str(f)]))
    assert report.checkpoint_summary(summ) is None


def test_audit_summary_from_stream(report, tmp_path):
    """The ISSUE-12 jaxpr-audit view: per-entry census-vs-counter
    deltas.  Agreement renders 'ok'; census > counted flags the entry
    as accounting drift (the uncounted-collective direction the
    static_audit gate fails on); counted > census annotates the benign
    custom_vjp re-trace direction."""
    import io

    f = tmp_path / "audit.jsonl"
    f.write_text(
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"audit.census.all_to_all","value":3,'
        '"tags":{"entry":"moe_ragged"}}\n'
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"audit.counted.all_to_all","value":3,'
        '"tags":{"entry":"moe_ragged"}}\n'
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"audit.census.ppermute","value":14,'
        '"tags":{"entry":"tp_ring_overlap"}}\n'
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"audit.counted.ppermute","value":12,'
        '"tags":{"entry":"tp_ring_overlap"}}\n')
    summ = report.summarize(report.load_records([str(f)]))
    audit = report.audit_summary(summ["counters"])
    assert audit is not None
    moe = audit["moe_ragged"]
    assert moe["drift"] is False
    assert moe["kinds"]["all_to_all"]["delta"] == 0
    ring = audit["tp_ring_overlap"]
    assert ring["drift"] is True
    assert ring["kinds"]["ppermute"]["delta"] == pytest.approx(2.0)
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "jaxpr audit (audit.*)" in text
    assert "moe_ragged: ok" in text
    assert "ACCOUNTING DRIFT" in text
    assert "uncounted collective" in text


def test_audit_summary_absent_without_series(report, tmp_path):
    f = tmp_path / "noaudit.jsonl"
    f.write_text('{"schema_version":3,"t":1,"type":"counter",'
                 '"name":"collectives.psum.calls","value":2}\n')
    summ = report.summarize(report.load_records([str(f)]))
    assert report.audit_summary(summ["counters"]) is None


def test_audit_summary_tier_c_row(report, tmp_path):
    """The ISSUE-13 tier-C row: audit.tierc.* counters from the
    concurrency_audit stress smoke render under the reserved 'tier_c'
    key with the zero-underflow / zero-new-findings gates deriving
    'clean'; the print section carries the row next to the jaxpr
    entries."""
    import io

    def stream(underflows, sketch_count=1600):
        return (
            '{"schema_version":3,"t":1,"type":"counter",'
            '"name":"audit.tierc.scrapes","value":120}\n'
            '{"schema_version":3,"t":1,"type":"counter",'
            '"name":"audit.tierc.flushes","value":90}\n'
            '{"schema_version":3,"t":1,"type":"counter",'
            '"name":"audit.tierc.saves","value":4}\n'
            '{"schema_version":3,"t":1,"type":"counter",'
            '"name":"audit.tierc.admits","value":388}\n'
            '{"schema_version":3,"t":1,"type":"counter",'
            f'"name":"audit.tierc.sketch_count","value":{sketch_count}}}\n'
            '{"schema_version":3,"t":1,"type":"counter",'
            '"name":"audit.tierc.sketch_expected","value":1600}\n'
            '{"schema_version":3,"t":1,"type":"counter",'
            '"name":"audit.tierc.scrape_parse_failures","value":0}\n'
            '{"schema_version":3,"t":1,"type":"counter",'
            '"name":"audit.tierc.refcount_underflows",'
            f'"value":{underflows}}}\n'
            '{"schema_version":3,"t":1,"type":"counter",'
            '"name":"audit.tierc.new_findings","value":0}\n')

    f = tmp_path / "tierc.jsonl"
    f.write_text(stream(underflows=0))
    summ = report.summarize(report.load_records([str(f)]))
    audit = report.audit_summary(summ["counters"])
    assert audit is not None
    tc = audit["tier_c"]
    assert tc["clean"] is True
    assert tc["stress"]["scrapes"] == 120
    assert tc["stress"]["admits"] == 388
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "tier C (concurrency stress): ok" in text
    assert "scrapes 120" in text

    # an underflow flips the row to FAILED — the report mirrors the
    # gate, it never launders a red smoke into an 'ok' line
    f.write_text(stream(underflows=2))
    summ = report.summarize(report.load_records([str(f)]))
    audit = report.audit_summary(summ["counters"])
    assert audit["tier_c"]["clean"] is False
    out = io.StringIO()
    report.print_report(summ, out=out)
    assert "FAILED — see the concurrency_audit gate" in out.getvalue()

    # a torn sketch (realized count != expected) also flips it — the
    # stream carries the REALIZED count, not the expected product
    f.write_text(stream(underflows=0, sketch_count=1599))
    summ = report.summarize(report.load_records([str(f)]))
    audit = report.audit_summary(summ["counters"])
    assert audit["tier_c"]["clean"] is False

    # tier-C counters alone (no jaxpr entries) still produce a report
    assert "moe_ragged" not in audit


def test_quantized_cache_summary_from_stream(report, tmp_path):
    """ISSUE 14 satellite: the dtype-tagged serving.cache_* gauges fold
    into bytes-per-resident-token per dtype, pool high-water, and —
    with both ablation dtypes in one stream — the implied admission
    multiple at matched pool bytes."""
    f = tmp_path / "quant.jsonl"
    rows = []
    for dtype, cb, cap, hw in (("bfloat16", 393216, 3072, 20),
                               ("int8", 391680, 5760, 38)):
        tags = '"tags":{"dtype":"%s"}' % dtype
        rows += [
            '{"schema_version":3,"t":1,"type":"gauge",'
            '"name":"serving.cache_bytes","value":%d,%s}' % (cb, tags),
            '{"schema_version":3,"t":2,"type":"gauge",'
            '"name":"serving.cache_capacity_tokens","value":%d,%s}'
            % (cap, tags),
            '{"schema_version":3,"t":3,"type":"gauge",'
            '"name":"serving.cache_blocks_hw","value":%d,%s}'
            % (hw, tags),
        ]
    f.write_text("\n".join(rows) + "\n")
    summ = report.summarize(report.load_records([str(f)]))
    # tagged gauges keep their tag suffix as distinct series
    assert "serving.cache_bytes{dtype=int8}" in summ["gauges"]
    q = report.quantized_cache_summary(summ)
    bf = q["dtypes"]["bfloat16"]
    i8 = q["dtypes"]["int8"]
    assert bf["bytes_per_token"] == 393216 / 3072   # 128 B/token
    assert i8["bytes_per_token"] == 391680 / 5760   # 68 B/token
    assert i8["pool_high_water_blocks"] == 38
    assert q["cheapest"] == "int8" and q["dearest"] == "bfloat16"
    assert abs(q["admission_multiple"] - 128 / 68) < 1e-9
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "quantized KV cache" in text
    assert "admission multiple at matched bytes" in text
    assert "1.88x" in text

    # one dtype only: per-dtype rows, no multiple
    single = report.quantized_cache_summary({
        "gauges": {"serving.cache_bytes{dtype=int8}": [100.0],
                   "serving.cache_capacity_tokens{dtype=int8}": [50.0]}})
    assert single["dtypes"]["int8"]["bytes_per_token"] == 2.0
    assert single["admission_multiple"] is None

    # a pre-ISSUE-14 stream -> no section
    assert report.quantized_cache_summary(
        {"gauges": {"serving.blocks_in_use": [1.0]}}) is None


def test_compile_cache_summary_from_stream(report, tmp_path):
    """ISSUE 17 satellite: the persistent compile-cache ledger gets a
    derived view — hit rate over load_or_compile calls, load-wall
    p50/p95 against the cumulative compile.ms ledger, warmup-ladder
    runs, and the worker READY wall — and an absent stream hides the
    section."""
    f = tmp_path / "cc.jsonl"
    f.write_text(
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"serving.compile_cache.hits","value":9}\n'
        '{"schema_version":3,"t":2,"type":"counter",'
        '"name":"serving.compile_cache.misses","value":3}\n'
        '{"schema_version":3,"t":3,"type":"observe",'
        '"name":"serving.compile_cache.load_ms","value":4.0}\n'
        '{"schema_version":3,"t":4,"type":"observe",'
        '"name":"serving.compile_cache.load_ms","value":6.0}\n'
        '{"schema_version":3,"t":5,"type":"observe",'
        '"name":"serving.compile_cache.load_ms","value":20.0}\n'
        '{"schema_version":3,"t":6,"type":"counter",'
        '"name":"compile.count","value":3}\n'
        '{"schema_version":3,"t":7,"type":"counter",'
        '"name":"compile.ms","value":5400.0}\n'
        '{"schema_version":3,"t":8,"type":"event",'
        '"name":"serving.compile_cache.warmup","value":1}\n'
        '{"schema_version":3,"t":9,"type":"gauge",'
        '"name":"worker.ready_ms","value":6200.0}\n'
        '{"schema_version":3,"t":10,"type":"gauge",'
        '"name":"worker.ready_ms","value":1800.0}\n')
    summ = report.summarize(report.load_records([str(f)]))
    cc = report.compile_cache_summary(summ)
    assert cc["hits"] == 9 and cc["misses"] == 3
    assert abs(cc["hit_rate"] - 0.75) < 1e-9
    # nearest-rank over [4, 6, 20]: p50 = 6, p95 = 20
    assert cc["load_ms"] == {"p50": 6.0, "p95": 20.0, "count": 3}
    assert cc["compile_count"] == 3
    assert cc["compile_ms_total"] == 5400.0
    assert cc["warmups"] == 1
    assert cc["ready_ms"]["count"] == 2
    assert cc["ready_ms"]["last"] == 1800.0
    assert cc["ready_ms"]["max"] == 6200.0
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "compile cache (serving.compile_cache.*)" in text
    assert "hit rate 0.75" in text
    assert "warmup ladders 1" in text
    assert "load ms p50 6" in text
    assert "XLA compiles 3" in text
    assert "worker READY ms last 1800" in text


def test_compile_cache_summary_reads_loads_beside_compiles(report,
                                                          tmp_path):
    """``compile.*`` counts the backend steps that compiled,
    ``compile.load.*`` those JAX's persistent cache served: the view
    reads both, and prints the loads' line only where there are any."""
    f = tmp_path / "cc.jsonl"
    f.write_text(
        '{"schema_version":3,"t":1,"type":"counter",'
        '"name":"serving.compile_cache.misses","value":2}\n'
        '{"schema_version":3,"t":2,"type":"counter",'
        '"name":"compile.count","value":1}\n'
        '{"schema_version":3,"t":3,"type":"counter",'
        '"name":"compile.ms","value":900.0}\n'
        '{"schema_version":3,"t":4,"type":"counter",'
        '"name":"compile.load.count","value":4}\n'
        '{"schema_version":3,"t":5,"type":"counter",'
        '"name":"compile.load.ms","value":36.0}\n')
    summ = report.summarize(report.load_records([str(f)]))
    cc = report.compile_cache_summary(summ)
    assert (cc["compile_count"], cc["compile_ms_total"]) == (1, 900.0)
    assert (cc["load_count"], cc["load_ms_total"]) == (4, 36.0)
    out = io.StringIO()
    report.print_report(summ, out=out)
    assert "persistent-cache loads 4 -> 36 ms total" in out.getvalue()


def test_compile_cache_summary_ready_only_and_absent(report):
    """A stream holding only worker.ready_ms (no-cache worker) still
    gets the READY row; a cache-free, READY-free stream hides the
    section entirely."""
    ready_only = report.compile_cache_summary({
        "counters": {}, "spans": {}, "events": {},
        "gauges": {"worker.ready_ms": [2500.0]}})
    assert ready_only["hit_rate"] is None
    assert ready_only["load_ms"] is None
    assert ready_only["ready_ms"]["last"] == 2500.0
    assert report.compile_cache_summary(
        {"counters": {"serving.requests": 4.0}, "spans": {},
         "events": {}, "gauges": {}}) is None


def test_host_tier_summary_from_stream(report, tmp_path):
    """ISSUE 18 satellite: the host-DRAM KV tier gets a derived view —
    take-side hit rate, the resume-vs-replay split of re-admissions,
    parked-bytes/pages high-water, page-in latency from the mergeable
    sketch, and fleet prefix-affinity routing hits."""
    import json

    sk_mod = report._load_sketch_module()
    sk = sk_mod.LogBucketSketch()
    for v in (2.0, 3.0, 9.0):
        sk.observe(v)
    recs = [
        {"type": "counter", "name": "serving.host_tier.hits",
         "value": 6},
        {"type": "counter", "name": "serving.host_tier.misses",
         "value": 2},
        {"type": "counter", "name": "serving.host_tier.evictions",
         "value": 1},
        {"type": "counter", "name": "serving.host_tier.page_ins",
         "value": 8},
        {"type": "counter", "name": "serving.host_tier.resumes",
         "value": 3},
        {"type": "counter", "name": "serving.host_tier.replays",
         "value": 1},
        {"type": "counter", "name": "cluster.prefix_affinity_hits",
         "value": 5},
        {"type": "gauge", "name": "serving.host_tier.bytes",
         "value": 1024.0},
        {"type": "gauge", "name": "serving.host_tier.bytes",
         "value": 4096.0},
        {"type": "gauge", "name": "serving.host_tier.bytes",
         "value": 2048.0},
        {"type": "gauge", "name": "serving.host_tier.pages",
         "value": 4.0},
        {"type": "sketch", "name": "serving.host_tier.page_in_ms",
         "value": sk.to_dict()},
    ]
    f = tmp_path / "ht.jsonl"
    f.write_text("".join(
        json.dumps(dict(r, schema_version=3, t=i)) + "\n"
        for i, r in enumerate(recs)))
    summ = report.summarize(report.load_records([str(f)]))
    ht = report.host_tier_summary(summ)
    assert ht["hits"] == 6 and ht["misses"] == 2
    assert abs(ht["hit_rate"] - 0.75) < 1e-9
    assert ht["resumes"] == 3 and ht["replays"] == 1
    assert abs(ht["resume_ratio"] - 0.75) < 1e-9
    assert ht["bytes_high_water"] == 4096.0
    assert ht["pages_high_water"] == 4.0
    assert ht["page_ins"] == 8 and ht["evictions"] == 1
    assert ht["prefix_affinity_hits"] == 5
    assert ht["page_in_ms"]["count"] == 3
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "host-DRAM KV tier (serving.host_tier.*)" in text
    assert "hit rate 0.75" in text
    assert "resume ratio 0.75" in text
    assert "page-in ms p50" in text
    assert "prefix-affinity routed dispatches 5" in text


def test_host_tier_summary_absent_without_series(report):
    """A stream with no host-tier series (tier off, older writers)
    hides the section entirely."""
    assert report.host_tier_summary(
        {"counters": {"serving.requests": 4.0}, "spans": {},
         "events": {}, "gauges": {}}) is None


def test_adapter_summary_from_stream(report, tmp_path):
    """ISSUE 20 satellite: the multi-tenant adapter pool gets a
    derived view — acquire-side hit rate, evictions, residency
    high-water, per-adapter request counts from the tagged
    serving.adapter.requests series, and fleet adapter-affinity
    routing hits."""
    import json

    recs = [
        {"type": "counter", "name": "serving.adapter.hits",
         "value": 9},
        {"type": "counter", "name": "serving.adapter.misses",
         "value": 3},
        {"type": "counter", "name": "serving.adapter.evictions",
         "value": 2},
        {"type": "counter", "name": "serving.adapter.requests",
         "tags": {"adapter": "1"}, "value": 7},
        {"type": "counter", "name": "serving.adapter.requests",
         "tags": {"adapter": "8"}, "value": 4},
        {"type": "counter", "name": "cluster.adapter_affinity_hits",
         "value": 6},
        {"type": "gauge", "name": "serving.adapter.resident",
         "value": 2.0},
        {"type": "gauge", "name": "serving.adapter.resident",
         "value": 4.0},
        {"type": "gauge", "name": "serving.adapter.resident",
         "value": 3.0},
        {"type": "gauge", "name": "serving.adapter.bytes",
         "value": 8192.0},
    ]
    f = tmp_path / "ad.jsonl"
    f.write_text("".join(
        json.dumps(dict(r, schema_version=3, t=i)) + "\n"
        for i, r in enumerate(recs)))
    summ = report.summarize(report.load_records([str(f)]))
    ad = report.adapter_summary(summ)
    assert ad["hits"] == 9 and ad["misses"] == 3
    assert abs(ad["hit_rate"] - 0.75) < 1e-9
    assert ad["evictions"] == 2
    assert ad["per_adapter"] == {"1": 7.0, "8": 4.0}
    assert ad["requests"] == 11 and ad["distinct_adapters"] == 2
    assert ad["resident_high_water"] == 4.0
    assert ad["bytes_high_water"] == 8192.0
    assert ad["adapter_affinity_hits"] == 6
    out = io.StringIO()
    report.print_report(summ, out=out)
    text = out.getvalue()
    assert "multi-tenant adapters (serving.adapter.*)" in text
    assert "hit rate 0.75" in text
    assert "requests 11 across 2 adapter(s)" in text
    assert "requests by adapter 1:7  8:4" in text
    assert "adapter-affinity routed dispatches 6" in text


def test_adapter_summary_absent_without_series(report):
    """A stream with no adapter series (pool off, older writers)
    hides the section entirely."""
    summ = {"counters": {"serving.requests": 4.0}, "spans": {},
            "events": {}, "gauges": {}}
    assert report.adapter_summary(summ) is None
    out = io.StringIO()
    report.print_report(dict(summ, sketches={}, truncated={},
                             unknown_schema=[], missing_schema=0),
                        out=out)
    assert "multi-tenant adapters" not in out.getvalue()


def test_host_tier_page_in_sketch_merges_across_hosts(
        aggregate, tmp_path):
    """ISSUE 18 satellite: serving.host_tier.page_in_ms rides the
    generic sketch-merge path — two hosts' cumulative flushes fold
    into one exact fleet quantile summary."""
    import json

    sk_mod = aggregate.load_sketch_module()

    def seg(values):
        sk = sk_mod.LogBucketSketch()
        for v in values:
            sk.observe(v)
        return (json.dumps({"type": "meta", "schema_version": 3})
                + "\n"
                + json.dumps({"type": "sketch",
                              "name": "serving.host_tier.page_in_ms",
                              "value": sk.to_dict()}) + "\n")

    a = tmp_path / "host_a.jsonl"
    b = tmp_path / "host_b.jsonl"
    a.write_text(seg([1.0, 2.0, 4.0]))
    b.write_text(seg([8.0, 16.0]))
    agg = aggregate.aggregate(
        aggregate.load_records([str(a), str(b)]))
    s = agg["sketches"]["serving.host_tier.page_in_ms"]
    assert s["count"] == 5
    assert s["max"] >= 16.0
