"""bin/osu_compare on two small artifacts of bin/bench_osu's shape: a
clean pair passes, a latency regression or a new adjacent-size cliff
fails, a name with "bw" gates as bandwidth, a missing file is bad input.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPARE = os.path.join(REPO, "bin", "osu_compare")
SIZES = [16384, 32768, 65536, 131072, 262144]


def _artifact(path, latency_scale=1.0, bw_scale=1.0, cliff_at=None):
    lat = {str(s): round((10.0 + s / 16384.0) * latency_scale, 2)
           for s in SIZES}
    if cliff_at is not None:
        lat[str(cliff_at)] = lat[str(cliff_at // 2)] * 10.0
    results = {
        "osu_latency_np2": lat,
        "osu_bw_np2": {str(s): (1000.0 + s / 100.0) * bw_scale
                       for s in SIZES},
        "osu_allreduce_np4": dict(lat),
    }
    with open(path, "w") as f:
        json.dump({"results": results}, f)
    return str(path)


# case -> (old artifact, new artifact or None for a missing file, exit
#          code, a word stdout must hold, exit code with the two swapped)
CASES = {
    "clean_pair": ({}, {"latency_scale": 1.02}, 0, "0 regression(s)", 0),
    "latency_regression": ({}, {"latency_scale": 1.30}, 1, "REGRESSION", 0),
    # both sides hold the cliff, so no row regresses: the guard looks at
    # the NEW artifact's neighbouring sizes alone
    "adjacent_size_cliff": ({"cliff_at": 65536}, {"cliff_at": 65536}, 1,
                            "CLIFF", 1),
    # only the "bw" rows differ: the drop fails and, swapped, the rise
    # passes, so the name was read as bandwidth, higher is better
    "bw_gates_as_bandwidth": ({}, {"bw_scale": 0.7}, 1, "REGRESSION", 0),
    "missing_file": ({}, None, 2, "", 2),
}


def _compare(old, new):
    return subprocess.run([sys.executable, COMPARE, old, new],
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("case", list(CASES))
def test_osu_compare(tmp_path, case):
    old_kw, new_kw, code, word, swapped = CASES[case]
    old = _artifact(tmp_path / "old.json", **old_kw)
    new = str(tmp_path / "new.json") if new_kw is None else \
        _artifact(tmp_path / "new.json", **new_kw)
    r = _compare(old, new)
    assert r.returncode == code, r.stdout + r.stderr
    assert word in r.stdout
    assert ("REGRESSION" in r.stdout) == (word == "REGRESSION")
    back = _compare(new, old)
    assert back.returncode == swapped, back.stdout + back.stderr
