"""`BENCHMARK.json`'s `per_layer` as PERF.md section 3 rules it: a quantity
is one entry, named by its metric file's base name, and lists its cells.

* the 128 (quantity, cell) pairs that the list of PR 39 read, one entry a
  pair, are the pairs that the list reads now (`PAIRS`: each cell's
  metric files, copied from `_metric_specs` at `bb9f028` before the edit);
* every pair is read, through its file and reader, from one made-up set
  of facts, and a quantity reads the same in every cell that lists it;
* `check_benchmark` passes the file as it stands and refuses a list with
  one fault put in, by the entry's name, before anything starts.
"""
import copy
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness                           # noqa: E402

PAIRS = {
    "c2m-10k.backlog": [
        "broker_wait_ms",
        "compiles_in_window",
        "device_idle_share",
        "engine_device_wait_share",
        "engine_host_ms",
        "engine_queue_wait_ms",
        "engine_starved_share",
        "evals_per_dispatch",
        "evals_per_job",
        "fsm_apply_ms",
        "http_register_ms",
        "invoke_scheduler_ms",
        "job_latency_p50_ms",
        "kernel_ms_per_alloc",
        "native_busy_share",
        "place_bulk_roofline",
        "plan_commit_ms",
        "plan_queue_wait_ms",
        "plan_submit_ms",
        "plans_partial_share",
        "register_rtt_ms",
        "sched_host_ms",
        "settle_wait_ms",
        "snapshot_ms",
        "steady_reuploads"
    ],
    "c2m-10k.spread-steady": [
        "broker_wait_ms",
        "compiles_in_window",
        "device_idle_share",
        "engine_device_wait_share",
        "engine_host_ms",
        "engine_queue_wait_ms",
        "engine_starved_share",
        "evals_per_dispatch",
        "evals_per_job",
        "fsm_apply_ms",
        "generator_late_p95_ms",
        "http_register_ms",
        "invoke_scheduler_ms",
        "job_placed_p95_ms",
        "kernel_ms_per_alloc",
        "native_busy_share",
        "place_scan_roofline",
        "plan_commit_ms",
        "plan_queue_wait_ms",
        "plan_submit_ms",
        "plans_partial_share",
        "register_rtt_ms",
        "sched_host_ms",
        "settle_wait_ms",
        "snapshot_ms",
        "steady_reuploads"
    ],
    "devices-10k.gpu-asks": [
        "broker_wait_ms",
        "compiles_in_window",
        "device_assign_ms",
        "device_fallback_share",
        "device_gate_wait_ms",
        "device_idle_share",
        "device_mask_ms",
        "engine_device_wait_share",
        "engine_host_ms",
        "engine_queue_wait_ms",
        "engine_starved_share",
        "evals_per_dispatch",
        "evals_per_job",
        "fsm_apply_ms",
        "generator_late_p95_ms",
        "http_register_ms",
        "invoke_scheduler_ms",
        "job_placed_p95_ms",
        "kernel_ms_per_alloc",
        "native_busy_share",
        "place_scan_roofline",
        "plan_commit_ms",
        "plan_queue_wait_ms",
        "plan_submit_ms",
        "plans_partial_share",
        "register_rtt_ms",
        "sched_host_ms",
        "settle_wait_ms",
        "snapshot_ms",
        "steady_reuploads"
    ],
    "preempt-10k.tiers": [
        "broker_wait_ms",
        "compiles_in_window",
        "device_idle_share",
        "engine_device_wait_share",
        "engine_host_ms",
        "engine_queue_wait_ms",
        "engine_starved_share",
        "evals_per_dispatch",
        "evals_per_job",
        "evictions_per_alloc",
        "followup_eval_ms",
        "fsm_apply_ms",
        "generator_late_p95_ms",
        "http_register_ms",
        "invoke_scheduler_ms",
        "job_placed_p95_ms",
        "kernel_ms_per_alloc",
        "native_busy_share",
        "place_bulk_roofline",
        "place_scan_roofline.evict",
        "plan_commit_ms",
        "plan_queue_wait_ms",
        "plan_submit_ms",
        "plans_partial_share",
        "preempt_build_ms",
        "preempt_find_ms",
        "preempt_passes",
        "preempt_search_ms",
        "register_rtt_ms",
        "sched_host_ms",
        "settle_wait_ms",
        "snapshot_ms",
        "steady_reuploads"
    ],
    "system-10k.fleet-rollout": [
        "compiles_in_window",
        "evals_per_job",
        "generator_late_p95_ms",
        "plan_queue_wait_ms",
        "plan_submit_ms",
        "preempt_build_ms",
        "preempt_find_ms",
        "preempt_search_ms",
        "preempt_searches_per_eval",
        "register_rtt_ms",
        "steady_reuploads",
        "system_diff_ms",
        "system_eval_ms",
        "system_place_ms"
    ]
}

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_the_literal_table_holds_128_pairs_of_five_cells():
    assert list(PAIRS) == CELLS
    assert sum(len(v) for v in PAIRS.values()) == 128


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reads_the_quantities_it_read(cell):
    read = sorted(spec["name"] for _m, spec in
                  harness._metric_specs(BENCH, cell))
    assert read == PAIRS[cell]


def test_entry_count():
    assert len(BENCH["per_layer"]) == 67 <= harness.PER_LAYER_MAX == 128
    moves = [m["moves"] for m in BENCH["per_layer"]]
    assert moves.count("allocs_per_s") == 25
    assert moves.count("job_placed_p50_ms") == 42   # 41 and the exception


def test_only_backlog_and_the_exception_carry_a_suffix():
    dotted = {m["name"] for m in BENCH["per_layer"] if "." in m["name"]}
    assert {n for n in dotted if not n.endswith(".backlog")} \
        == {"place_scan_roofline.evict"}
    for m in BENCH["per_layer"]:
        if m["name"].endswith(".backlog"):
            assert m["moves"] == "allocs_per_s"
            assert m["workloads"] == ["c2m-10k.backlog"]


def test_workloads_are_in_the_order_of_the_cells():
    for m in BENCH["per_layer"]:
        assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]


def _facts() -> dict:
    """Every fact a metric file names, each with a value of its own."""
    keys = ["shape.rows", "shape.resource_dims"]
    for f in sorted(os.listdir(os.path.join(harness.HERE, "metrics"))):
        with open(os.path.join(harness.HERE, "metrics", f)) as fh:
            spec = json.load(fh)
        for k in ("num", "den", "steps"):
            keys += spec.get(k, [])
        keys += [spec[k] for k in ("part", "whole") if k in spec]
        if "kernel" in spec:
            keys.append(f"trace.kernel_s.{spec['kernel']}")
    facts = {k: 1000.0 + 7.0 * i for i, k in enumerate(dict.fromkeys(keys))}
    facts["device.kind"] = "TPU v5 lite"
    return facts


def test_every_pair_is_read_and_a_quantity_reads_the_same_in_every_cell():
    facts = _facts()
    by_file: dict = {}
    for cell in CELLS:
        got = harness._per_layer(BENCH, cell, facts)
        listed = [m["name"] for m in BENCH["per_layer"]
                  if cell in m["workloads"]]
        assert sorted(got) == sorted(listed)
        assert len(got) == len(PAIRS[cell])
        for m, spec in harness._metric_specs(BENCH, cell):
            value = got[m["name"]]
            assert value["unit"] == m["unit"] == spec["unit"]
            by_file.setdefault(spec["name"], set()).add(value["value"])
    # one file, one reader, one value: under `x` in every cell of its list
    # and under `x.backlog`
    assert {k: len(v) for k, v in by_file.items()} \
        == {k: 1 for k in by_file}
    assert len(by_file) == 43
    # and no two files read the same number from facts that all differ
    assert len({v for vs in by_file.values() for v in vs}) == 43


# ------------------------------------------------- check_benchmark

def _entry(name, **kw):
    return {"name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "raft + state store",
            "moves": "job_placed_p50_ms",
            "workloads": ["devices-10k.gpu-asks"], **kw}


def _by_name(bench, name):
    return next(m for m in bench["per_layer"] if m["name"] == name)


def _twice(b):
    b["per_layer"].append(copy.deepcopy(_by_name(b, "fsm_apply_ms")))


def _no_list(b):
    del _by_name(b, "fsm_apply_ms")["workloads"]


def _unknown_cell(b):
    _by_name(b, "fsm_apply_ms")["workloads"].append("c2m-10k.no-such")


def _moves_nothing(b):
    _by_name(b, "fsm_apply_ms")["moves"] = "job_placed_p95_ms"


def _cell_without_the_moved_metric(b):
    _by_name(b, "fsm_apply_ms")["workloads"].insert(0, "c2m-10k.backlog")


def _copy_a_cell(b):
    b["per_layer"].append(_entry("fsm_apply_ms.gpu"))


def _two_copies(b):
    b["per_layer"].append(_entry("fsm_apply_ms.backlog2",
                                 moves="allocs_per_s",
                                 workloads=["c2m-10k.backlog"]))


def _no_file(b):
    b["per_layer"].append(_entry("nothing_ms"))


def _no_reader(b):
    b["per_layer"].append(_entry("bad_reader_ms"))


def _overfull(b):
    b["per_layer"] += [_entry(f"filler_{i}_ms") for i in range(62)]


FAULTS = {
    "a name twice": (_twice, "'fsm_apply_ms' twice"),
    "no workloads list": (_no_list, "'fsm_apply_ms' lists no workloads"),
    "a cell that is none": (_unknown_cell, "'fsm_apply_ms'.*no-such"),
    "moves no end-to-end metric": (_moves_nothing,
                                   "'fsm_apply_ms'.*job_placed_p95_ms"),
    "a cell that does not report what it moves": (
        _cell_without_the_moved_metric, "'fsm_apply_ms'.*c2m-10k.backlog"),
    "a cell's copy beside its quantity": (_copy_a_cell,
                                          "'fsm_apply_ms.gpu'"),
    "two copies of one base name and moves": (_two_copies,
                                              "'fsm_apply_ms.backlog"),
    "no metric file": (_no_file, "'nothing_ms' has no metric file"),
    "a reader that does not import": (_no_reader,
                                      "'bad_reader_ms'.*no_such_reader"),
    "a 129th entry": (_overfull, "129 entries.*'filler_61_ms'"),
}


@pytest.fixture
def metrics_with_a_bad_reader(tmp_path, monkeypatch):
    """The metric files as they stand and one whose reader is no module."""
    shutil.copytree(os.path.join(harness.HERE, "metrics"),
                    tmp_path / "metrics")
    with open(tmp_path / "metrics" / "bad_reader_ms.json", "w") as f:
        json.dump({"name": "bad_reader_ms", "reader": "no_such_reader"}, f)
    monkeypatch.setattr(harness, "HERE", str(tmp_path))


def test_the_list_as_it_stands_passes(metrics_with_a_bad_reader):
    harness.check_benchmark(BENCH)


def test_the_exception_passes_because_it_has_a_file_of_its_own():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert {"place_scan_roofline", "place_scan_roofline.evict"} <= set(names)
    for name, file in (
            ("place_scan_roofline.evict", "place_scan_roofline.evict.json"),
            ("place_bulk_roofline.backlog", "place_bulk_roofline.json")):
        assert os.path.basename(harness._metric_file(name)) == file
    harness.check_benchmark(BENCH)


@pytest.mark.parametrize("fault", FAULTS)
def test_one_fault_is_refused_by_the_entrys_name(
        fault, metrics_with_a_bad_reader):
    put_in, message = FAULTS[fault]
    bench = copy.deepcopy(BENCH)
    put_in(bench)
    with pytest.raises(harness.Refused, match=message):
        harness.check_benchmark(bench)


@pytest.mark.parametrize("fault", ["a name twice", "a 129th entry"])
def test_run_exits_2_before_it_looks_for_a_chip(fault, monkeypatch, capsys):
    from benchmark import run
    bench = copy.deepcopy(BENCH)
    FAULTS[fault][0](bench)
    monkeypatch.setattr(harness, "load_benchmark", lambda: bench)
    monkeypatch.setattr(harness, "device_check", None)   # a call would raise
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")  # run.py sets it
    assert run.main(["--workload", "c2m-10k.backlog", "--seed", "1",
                     "--seconds", "1"]) == 2
    err = capsys.readouterr()
    assert err.out == ""
    name = "fsm_apply_ms" if fault == "a name twice" else "filler_61_ms"
    assert "refused" in err.err and name in err.err
