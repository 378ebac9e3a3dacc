"""Telemetry registry tests (r12 satellites): uniform reservoir
sampling and well-formed Prometheus exposition."""
import math
import random

from nomad_tpu.telemetry import MetricsRegistry, _Sample


def test_reservoir_is_uniform_over_the_whole_series():
    """Algorithm R keeps every observation with equal probability.  Feed
    a series whose value IS its index: a uniform reservoir's mean sits
    near the series midpoint; the old `count % 1024` ring kept only the
    most recent window, whose mean sits near the end."""
    n = 50_000
    s = _Sample()
    for i in range(n):
        s.add(float(i))
    assert s.count == n
    assert len(s.values) == 1024
    mean = sum(s.values) / len(s.values)
    # midpoint is (n-1)/2 = 24999.5; a last-window ring would sit at
    # ~49487.  1024 uniform draws from U(0, n) have stddev of the mean
    # ~ n/sqrt(12)/32 ~ 451, so +/-6 sigma is a comfortable, non-flaky
    # band that still rules the ring out by ~40 sigma.
    mid = (n - 1) / 2.0
    band = 6.0 * n / math.sqrt(12.0) / math.sqrt(1024.0)
    assert abs(mean - mid) < band, mean

    # percentiles follow: p50 of a uniform 0..n series is ~n/2, where
    # the ring's p50 was pinned inside the last 1024 values
    p50 = s.summary()["p50"]
    assert abs(p50 - mid) < 4_000, p50


def test_reservoir_every_index_can_survive():
    """Spot-check the survival mechanics: early values are not always
    evicted (the ring overwrote slot `count % 1024` deterministically,
    so value i never outlived step i + 1024)."""
    survived_early = 0
    for seed in range(20):
        s = _Sample()
        s._rng = random.Random(seed)
        for i in range(10_000):
            s.add(float(i))
        if any(v < 1024 for v in s.values):
            survived_early += 1
    assert survived_early > 0


def test_prometheus_exposition_shape():
    reg = MetricsRegistry()
    reg.incr("nomad.rpc.request", 3)
    reg.set_gauge("nomad.broker.total_ready", 7)
    reg.add_sample("nomad.plan.submit", 12.5)
    text = reg.prometheus()
    lines = text.splitlines()

    # counters carry the conventional _total suffix
    assert "nomad_rpc_request_total 3.0" in lines
    assert not any(line.startswith("nomad_rpc_request ")
                   for line in lines)
    # every family has exactly one HELP immediately before its TYPE
    for name, kind in (("nomad_rpc_request_total", "counter"),
                       ("nomad_broker_total_ready", "gauge"),
                       ("nomad_plan_submit", "summary")):
        helps = [i for i, ln in enumerate(lines)
                 if ln.startswith(f"# HELP {name} ")]
        assert len(helps) == 1, (name, helps)
        ti = lines.index(f"# TYPE {name} {kind}")
        assert helps[0] == ti - 1, (name, helps, ti)
    assert 'nomad_plan_submit{quantile="0.5"} 12.5' in lines
    assert "nomad_plan_submit_count 1" in lines


def test_prometheus_sanitization_collision_detected():
    """`a.b` and `a-b` both sanitize to `a_b`: exactly one family may be
    exported — duplicate TYPE blocks make scrapers reject the whole
    page — and the skipped name must be called out."""
    reg = MetricsRegistry()
    reg.set_gauge("a.b", 1)
    reg.set_gauge("a-b", 2)
    text = reg.prometheus()
    assert text.count("# TYPE a_b gauge") == 1
    assert "collision" in text
    # the surviving family still has a value line
    assert sum(1 for line in text.splitlines()
               if line.startswith("a_b ")) == 1


def test_span_sample_gives_back_total_and_count():
    """What `benchmark/harness.snapshot` differences: a Sample's `count`
    and `mean * count` are the number of spans and their summed ms."""
    import time

    from nomad_tpu import tracing
    from nomad_tpu.telemetry import global_metrics

    spans = []
    for _ in range(3):
        with tracing.span("ttel.total") as sp:
            time.sleep(0.003)
        spans.append(sp)
    (got,) = [s for s in global_metrics.snapshot()["Samples"]
              if s["Name"] == "nomad.ttel.total"]
    assert got["count"] == 3
    assert abs(got["mean"] * got["count"]
               - sum(sp.seconds for sp in spans) * 1e3) < 1e-6
    assert got["max"] == max(sp.seconds for sp in spans) * 1e3


def test_span_names_export_without_sanitisation_collision(spine_metrics):
    """Every Sample a job's spans wrote (`nomad.<name>`,
    `nomad.self.<name>`) is one summary family of the Prometheus page;
    none is skipped for sanitising to another's name."""
    lines = spine_metrics["prometheus"].splitlines()
    names = [n for n in spine_metrics["samples"] if n.startswith("nomad.")]
    assert len(names) >= 30
    assert not [ln for ln in lines if ln.startswith("# collision")
                and any(repr(n) in ln for n in names)]
    for n in names:
        fam = n.replace(".", "_").replace("-", "_")
        assert lines.count(f"# TYPE {fam} summary") == 1, n
        assert any(ln.startswith(f"{fam}_count ") for ln in lines), n
