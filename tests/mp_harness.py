"""Real multi-process conformance harness for the TALP collection layer.

Launches N independent ``python -m repro.launch.train`` (or ``serve``)
processes — actual OS processes with their own interpreters, JAX
runtimes and clocks, not threads or in-process simulations — against one
shared spool directory, then hands the spool back to the test for
validation. This is the harness the ROADMAP's "validate on a real
multi-process fleet" open item asks for: the transports get exercised by
genuinely concurrent producers racing on a real filesystem.

Also hosts the ``jax.distributed`` fleet runner used by the (opt-in)
``AllGatherTransport`` conformance test: every rank initializes the
distributed runtime against a shared coordinator and exchanges its
result through the real collective.

Importable from tests (``from mp_harness import ...``) and runnable
standalone for debugging::

    PYTHONPATH=src python tests/mp_harness.py --ranks 3 --spool /tmp/spool
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC_DIR = os.path.join(REPO_ROOT, "src")

#: Tiny-but-real sizes: enough steps for every TALP state to be charged,
#: small enough that a 3-rank fleet finishes in seconds on CPU. The
#: global batch of 6 divides every fleet size the harness is used with
#: (1, 2 and 3 ranks).
SMOKE_ARCH = "llama3.2-3b"
SMOKE_ARGS = ("--steps", "3", "--batch", "6", "--seq", "16")


def fleet_env(cache_dir: str) -> Dict[str, str]:
    """Subprocess environment: repo sources importable, CPU-only JAX (a
    chip belongs to one process, so rank fleets are CPU fleets), and the
    drivers' compilation cache in ``cache_dir``, a directory the caller
    owns, so that a run leaves nothing in the checkout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC_DIR + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    return env


@dataclass
class RankRun:
    """One finished rank process."""

    rank: int
    returncode: int
    stdout: str
    stderr: str

    @property
    def ok(self) -> bool:
        return self.returncode == 0


@dataclass
class FleetResult:
    runs: List[RankRun] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(r.ok for r in self.runs)

    def failures(self) -> List[RankRun]:
        return [r for r in self.runs if not r.ok]

    def report(self) -> str:
        lines = []
        for r in self.runs:
            lines.append(f"--- rank {r.rank} exit {r.returncode} ---")
            if not r.ok:
                lines.append(r.stdout[-2000:])
                lines.append(r.stderr[-2000:])
        return "\n".join(lines)


def launch_fleet(
    spool_dir: str,
    n_ranks: int = 3,
    driver: str = "repro.launch.train",
    extra_args: Sequence[str] = (),
    per_rank_args: Optional[Dict[int, Sequence[str]]] = None,
    timeout: float = 300.0,
    env_extra: Optional[Dict[str, str]] = None,
) -> FleetResult:
    """Spawn ``n_ranks`` concurrent driver processes sharing one spool.

    Every rank gets ``--rank i --world-size n --talp-spool <dir>`` plus
    the tiny smoke sizes; ``extra_args`` append to every rank,
    ``per_rank_args[i]`` to rank *i* only (how fault-plan flags reach a
    single rank). Processes are launched together and awaited together —
    the ranks genuinely race on the shared spool directory. The
    compilation cache goes next to the spool, in ``jax_cache``.
    """
    env = fleet_env(os.path.join(
        os.path.dirname(os.path.abspath(spool_dir)), "jax_cache"))
    if env_extra:
        env.update(env_extra)
    procs = []
    for rank in range(n_ranks):
        cmd = [
            sys.executable, "-m", driver, "--arch", SMOKE_ARCH, "--smoke",
            *SMOKE_ARGS,
            "--rank", str(rank), "--world-size", str(n_ranks),
            "--talp-spool", spool_dir,
            *extra_args,
            *(per_rank_args or {}).get(rank, ()),
        ]
        procs.append((rank, subprocess.Popen(
            cmd, env=env, cwd=REPO_ROOT, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )))
    result = FleetResult()
    for rank, proc in procs:
        try:
            out, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
            result.runs.append(RankRun(rank, -9, out, err + "\n[timeout]"))
            continue
        result.runs.append(RankRun(rank, proc.returncode, out, err))
    return result


# ---------------------------------------------------------------------------
# jax.distributed allgather fleet
# ---------------------------------------------------------------------------
#: Worker body run by every process of the allgather fleet: initialize
#: the distributed runtime, build a deterministic per-rank result, push
#: it through the *real* collective, write the merged job JSON.
_ALLGATHER_WORKER = r"""
import sys
rank, n_proc, coordinator, out_path = (
    int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
)
import jax
jax.distributed.initialize(
    coordinator_address=coordinator, num_processes=n_proc, process_id=rank
)
from repro.core import DeviceActivity
from repro.core.merge import AllGatherTransport
from repro.core.report import to_json
from repro.core.talp import TalpMonitor

class Clock:
    def __init__(self): self.t = 0.0
    def __call__(self): return self.t
    def advance(self, dt): self.t += dt

clk = Clock()
mon = TalpMonitor(f"rank{rank}", rank=rank, clock=clk)
with mon.region("step"):
    clk.advance(1.0 + rank)
    with mon.offload():
        clk.advance(0.5)
mon.add_device_record(0, DeviceActivity.KERNEL, 0.0, 0.25 * (rank + 1))
job = AllGatherTransport().gather(mon.finalize(), name="job")
with open(out_path, "w") as f:
    f.write(to_json(job))
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_allgather_fleet(
    out_dir: str, n_ranks: int = 2, timeout: float = 300.0
) -> FleetResult:
    """Run an N-process ``jax.distributed`` fleet through the real
    ``AllGatherTransport`` collective; each rank writes the job report it
    obtained to ``<out_dir>/job_rank<i>.json`` (every rank must obtain
    the identical merged result)."""
    env = fleet_env(os.path.join(out_dir, "jax_cache"))
    coordinator = f"127.0.0.1:{free_port()}"
    procs = []
    for rank in range(n_ranks):
        out_path = os.path.join(out_dir, f"job_rank{rank}.json")
        procs.append((rank, subprocess.Popen(
            [sys.executable, "-c", _ALLGATHER_WORKER, str(rank),
             str(n_ranks), coordinator, out_path],
            env=env, cwd=REPO_ROOT, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )))
    result = FleetResult()
    for rank, proc in procs:
        try:
            out, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
            result.runs.append(RankRun(rank, -9, out, err + "\n[timeout]"))
            continue
        result.runs.append(RankRun(rank, proc.returncode, out, err))
    return result


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ranks", type=int, default=3)
    ap.add_argument("--spool", required=True)
    ap.add_argument("--driver", default="repro.launch.train")
    ap.add_argument("--fault-plan", default=None,
                    help="FaultPlan spec forwarded to every rank as "
                         "--talp-fault-plan (JSON, @file, or path)")
    ap.add_argument("--extra", nargs="*", default=[])
    args = ap.parse_args()
    extra = list(args.extra)
    if args.fault_plan:
        extra += ["--talp-fault-plan", args.fault_plan]
    res = launch_fleet(args.spool, n_ranks=args.ranks, driver=args.driver,
                       extra_args=extra)
    print(res.report() or f"all {args.ranks} rank(s) exited 0")
    sys.exit(0 if res.ok else 1)


if __name__ == "__main__":
    main()
