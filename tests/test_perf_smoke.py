"""Tier-1 perf smoke: the large-message datapath must stay fast.

A 4-rank 1 MiB allreduce through the arena/CMA sectioned exchange runs
at ~2-3 ms/call on a one-core host; the per-send scratch-file path it
replaced was ~33 ms there. The 5 s budget for ten
timed iterations is generous enough to be variance-proof on an
oversubscribed CI host while still failing hard if the scratch-file
cliff (or any comparable per-send staging cost) silently returns.

bin/osu_compare diffs two bin/bench_osu runs size by size; this test
is the always-on tripwire in the tier-1 lane.
"""

import os
import re
import subprocess
import sys

BUDGET_S = 5.0
ITERS = 10

# small-message budgets (us/call): the measured numbers on the 1-core
# bench host are ~150 us half-RTT / ~260 us per 4-byte allreduce for
# python-API ranks; 10x headroom keeps the check variance-proof while
# still failing hard on an interpreter-path or spin-schedule cliff
# (the r5 regressions were 3-15x).
PINGPONG_BUDGET_US = 2000.0
TINY_ALLREDUCE_BUDGET_US = 5000.0


def _run_prog(name, np_):
    prog = os.path.join(os.path.dirname(__file__), "progs", name)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", "mvapich2_tpu.run", "-np",
                       str(np_), sys.executable, prog], cwd=repo,
                       capture_output=True, text=True, timeout=600,
                       env=env)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "No Errors" in r.stdout, f"{r.stdout}\n{r.stderr}"
    return r.stdout


def test_smallmsg_np4_under_budget():
    """Tier-1 tripwire for the small-message datapath: 8-byte pingpong
    and 4-byte allreduce at np=4 (process mode, shm plane + flat-slot
    collective tier) stay inside generous wall budgets."""
    out = _run_prog("smallmsg_smoke_prog.py", 4)
    pp = re.search(r"pingpong_8B_halfrtt_us=([0-9.]+)", out)
    ar = re.search(r"allreduce_4B_avg_us=([0-9.]+)", out)
    assert pp and ar, f"no timing lines in output:\n{out}"
    pp_us, ar_us = float(pp.group(1)), float(ar.group(1))
    assert pp_us < PINGPONG_BUDGET_US, (
        f"8 B pingpong too slow: {pp_us:.0f} us half-RTT "
        f"(budget {PINGPONG_BUDGET_US:.0f}) — spin schedule or "
        f"eager path regressed?")
    assert ar_us < TINY_ALLREDUCE_BUDGET_US, (
        f"4 B allreduce too slow: {ar_us:.0f} us/call "
        f"(budget {TINY_ALLREDUCE_BUDGET_US:.0f}) — flat-slot tier "
        f"not engaged?")


def test_allreduce_1mib_np4_under_budget():
    prog = os.path.join(os.path.dirname(__file__), "progs",
                        "allreduce_smoke_prog.py")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", "mvapich2_tpu.run", "-np",
                       "4", sys.executable, prog], cwd=repo,
                       capture_output=True, text=True, timeout=600,
                       env=env)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "No Errors" in r.stdout, f"{r.stdout}\n{r.stderr}"
    m = re.search(r"allreduce_1MiB_avg_us=([0-9.]+)", r.stdout)
    assert m, f"no timing line in output:\n{r.stdout}"
    avg_us = float(m.group(1))
    total_s = avg_us * ITERS / 1e6
    assert total_s < BUDGET_S, (
        f"1 MiB allreduce too slow: {avg_us:.0f} us/call "
        f"({total_s:.2f} s for {ITERS} iters, budget {BUDGET_S} s) — "
        f"did the per-send scratch-file path come back?")
