"""The yardstick's arithmetic, the generator and the comparison."""

import json
import os

import numpy as np
import pytest

from chipbench import check, generator, harness, stats

MiB = 1 << 20
TRAFFIC = {"values": {"kind": "uniform_int", "lo": -2**20, "hi": 2**20}}


def allreduce():
    return harness.load_by_name("collectives", "allreduce")


def test_bus_factor_and_bandwidth():
    coll = allreduce()
    assert coll.bus_factor(8) == 1.75 and coll.bus_factor(4) == 1.5
    # 4 ranks x 64 MiB, 1000 calls in 5 s: 1.5 * 67108864 * 1000 / 5 / 1e9
    got = stats.bus_bandwidth_GBps(coll.bus_factor(4), 64 * MiB, 1000, 5.0)
    assert got == pytest.approx(20.1326592)


def test_least_bytes_and_roofline_floor():
    coll = allreduce()
    peaks = harness.read_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    nbytes, key = coll.least_bytes("slot", 8, 64 * MiB)
    assert (nbytes, key) == (9 * 64 * MiB, "hbm_GBps")
    assert nbytes / (peaks[key] * 1e9) == pytest.approx(737.46e-6, rel=1e-4)
    nbytes, key = coll.least_bytes("ring", 4, 64 * MiB)
    assert (nbytes, key) == (96 * MiB, "ici_GBps")
    assert nbytes / (peaks[key] * 1e9) == pytest.approx(503.3e-6, rel=1e-4)
    with pytest.raises(KeyError):
        coll.least_bytes("tree", 4, 1)


def test_iteration_latency_is_the_max_over_ranks():
    lat = stats.iteration_latency_us([[1e-3, 5e-3, 2e-3], [2e-3, 1e-3, 2e-3]])
    assert lat.tolist() == pytest.approx([2000.0, 5000.0, 2000.0])
    e2e = stats.end_to_end(lat, 1.75, 4096, 0.01, 3.0)
    assert e2e["lat_us_p50"] == pytest.approx(2000.0)
    assert e2e["lat_us_p95"] == pytest.approx(4700.0)
    assert e2e["busbw_GBps"] == pytest.approx(1.75 * 4096 * 3 / 0.01 / 1e9)
    assert e2e["setup_s"] == 3.0


def test_inputs_follow_the_seed_and_sum_exactly():
    big = 2**31 + 11
    a = generator.make_input(TRAFFIC, big, 3, 4096, np.dtype("float32"))
    b = generator.make_input(TRAFFIC, big, 3, 4096, np.dtype("float32"))
    c = generator.make_input(TRAFFIC, big, 4, 4096, np.dtype("float32"))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.float32 and np.all(a == np.round(a))
    assert np.abs(a).max() <= 2**20
    xs = [generator.make_input(TRAFFIC, 9, r, 4096, np.dtype("float32"))
          for r in range(8)]
    ref = allreduce().reference(xs)[0]
    exact = np.sum(np.asarray(xs, dtype=np.float64), axis=0)
    assert np.array_equal(ref.astype(np.float64), exact)
    assert np.array_equal(ref, np.sum(xs[::-1], axis=0))     # any order


def test_comparison_passes_the_reference_and_fails_bfloat16():
    coll = allreduce()
    xs = [generator.make_input(TRAFFIC, 21, r, 16384, np.dtype("float32"))
          for r in range(8)]
    ref = coll.reference(xs)
    sound = check.compare_results("sound", [r.copy() for r in ref], ref)
    assert check.verdict(sound) and [c.value for c in sound] == [0, 0.0]
    control = check.compare_results("control", coll.lower_precision(xs), ref)
    assert not check.verdict(control)
    # bf16 keeps 8 bits of a 21-bit integer: nearly every element moves
    assert control[0].value > 0.9 * 16384 and control[1].value >= 2**10


@pytest.mark.parametrize("fault", ["one element", "one rank missing", "dtype"])
def test_comparison_sees_small_faults(fault):
    ref = [np.arange(256, dtype=np.float32)] * 4
    got = [r.copy() for r in ref]
    if fault == "one element":
        got[2][17] += 1
    elif fault == "one rank missing":
        got[1] = None
    else:
        got[0] = got[0].astype(np.float64)
    assert not check.verdict(check.compare_results("x", got, ref))


@pytest.mark.parametrize("broken", ["level", "fallback", "compiled", "cache",
                                    "off_device", "none"])
def test_count_guards(broken):
    args = dict(ranks=8, calls_per_rank=10, level_rise={"coll_level_chip": 80},
                fallback_rise={"dev_coll_fallback_vmem": 0},
                compiles_in_window=0, cache_before=4, cache_after=4,
                off_device=0)
    if broken == "level":
        args["level_rise"] = {"coll_level_chip": 72}
    elif broken == "fallback":
        args["fallback_rise"] = {"dev_coll_fallback_vmem": 1}
    elif broken == "compiled":
        args["compiles_in_window"] = 1
    elif broken == "cache":
        args["cache_after"] = 5
    elif broken == "off_device":
        args["off_device"] = 1
    assert check.verdict(check.compare_counts(**args)) == (broken == "none")


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}"


def test_benchmark_json_keeps_the_contract_and_finds_its_files():
    import re
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(bench)) < 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.fullmatch(NAME, c["name"])
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        assert all(re.fullmatch(NAME, k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert c["name"] in {w["config"] for w in bench["workloads"]}
    four = 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.fullmatch(NAME, w["name"]) and re.fullmatch(NAME, w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        four += w["chips"] == 4
        t = harness.read_json(harness.HERE, "traffic", w["traffic"] + ".json")
        harness.load_by_name("collectives", t["collective"])
    assert four <= max(1, len(cells) // 2)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        mod = harness.load_by_name("layer_metrics", m["name"])
        assert mod.NAME == m["name"] and callable(mod.compute)
        layers.add(m["layer"])
        # every cell that reports the metric reports what it moves
        moved = harness.by_name(bench["end_to_end"], m["moves"], "metric")
        for cell in m.get("workloads", cells):
            assert harness.reported_in(moved, cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert re.fullmatch(NAME, m["name"])
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        assert sum(harness.reported_in(m, cell)
                   for m in bench["end_to_end"]) >= 2
        assert any(harness.reported_in(m, cell) for m in bench["per_layer"])
    perf = open(os.path.join(harness.ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf
