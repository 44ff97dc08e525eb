"""Multi-process conformance: real ``launch/train.py`` rank fleets
exercising the collection transports end to end (see ``mp_harness``).

These are the tests that close the ROADMAP's "validate on a real
multi-process fleet" open item: N actual OS processes race on one spool
directory, and the job-level report they produce must agree bit-for-bit
with the in-process reference merge of the same per-rank payloads.
"""

import json
import os
import subprocess
import sys

import pytest

from mp_harness import (
    FleetResult,
    fleet_env,
    launch_allgather_fleet,
    launch_fleet,
)

from repro.core.merge import InProcessGather, load_spool_payload
from repro.core.report import to_json


def _assert_fleet_ok(res: FleetResult) -> None:
    assert res.ok, res.report()


@pytest.mark.slow
def test_three_rank_fleet_matches_in_process_merge(tmp_path):
    """3 subprocess ranks running ``launch/train.py --talp-spool`` must
    produce a merged job report bit-identical to an in-process 3-rank
    :class:`InProcessGather` merge of the same spooled payloads."""
    spool = tmp_path / "spool"
    res = launch_fleet(str(spool), n_ranks=3)
    _assert_fleet_ok(res)

    job_path = spool / "talp_job.json"
    assert job_path.exists(), "no rank merged the completed spool"
    fleet_json = job_path.read_text()

    gather = InProcessGather(world_size=3)
    for rank in range(3):
        payload = spool / f"talp_rank{rank:05d}.npz"
        assert payload.exists(), f"rank {rank} left no spool payload"
        gather.submit(load_spool_payload(str(payload))[0], rank=rank)
    assert gather.ready()
    reference_json = to_json(gather.merge(name="train"))

    assert fleet_json == reference_json  # bit-identical, not approx
    job = json.loads(fleet_json)
    g = job["regions"]["Global"]
    assert len(g["host_states"]) == 3
    assert g["host_metrics"]["parallel_efficiency"] > 0


@pytest.mark.slow
def test_fault_injected_fleet_partial_merge(tmp_path):
    """The acceptance scenario against a *real* fleet: rank 2 drops its
    submit, rank 1's payload is truncated mid-file by the fault plan.
    Every rank process still exits 0, and the post-mortem tolerant merge
    CLI reports both losses while reproducing the surviving rank's
    metrics bit-identically to a clean merge of that rank."""
    from repro.core.merge import merge_results

    spool = tmp_path / "spool"
    plan = json.dumps({"drop": [2], "truncate": {"1": 200}})
    res = launch_fleet(
        str(spool), n_ranks=3, extra_args=("--talp-fault-plan", plan)
    )
    _assert_fleet_ok(res)

    # The fleet could not self-merge: rank 2 never submitted.
    assert not (spool / "talp_job.json").exists()
    assert not (spool / "talp_rank00002.npz").exists()

    # Clean reference for the surviving rank, read before the tolerant
    # merge quarantines its corrupt neighbour.
    survivor = load_spool_payload(str(spool / "talp_rank00000.npz"))[0]
    reference = json.loads(to_json(merge_results([survivor], name="job")))

    out = tmp_path / "job.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.core.merge", str(spool),
         "--name", "job", "--allow-missing-ranks", "--expected-ranks", "3",
         "--json-out", str(out)],
        capture_output=True, text=True,
        env=fleet_env(str(tmp_path / "jax_cache")),
    )
    assert proc.returncode == 0, proc.stderr
    job = json.loads(out.read_text())
    cov = job["rank_coverage"]
    assert cov["expected"] == 3
    assert cov["merged"] == [0]
    assert cov["missing"] == [2]
    assert [q["rank"] for q in cov["quarantined"]] == [1]
    assert cov["quarantined"][0]["reason"]
    # surviving-rank metrics bit-identical to the clean merge
    assert job["regions"] == reference["regions"]
    # the corrupted payload was moved aside, not deleted
    assert (spool / "quarantine" / "talp_rank00001.npz").exists()


@pytest.mark.slow
@pytest.mark.skipif(
    not os.environ.get("TALP_MP_ALLGATHER"),
    reason="multi-process jax.distributed fleet is opt-in: set "
           "TALP_MP_ALLGATHER=1 (needs a JAX build whose distributed "
           "runtime supports multi-process CPU fleets)",
)
def test_allgather_transport_real_fleet(tmp_path):
    """2 real ``jax.distributed`` processes exchange their results via
    the actual ``process_allgather`` collective; every rank must obtain
    the identical job report, equal to the in-process reference merge."""
    from repro.core.merge import merge_results
    from repro.core.talp import TalpMonitor
    from repro.core import DeviceActivity

    res = launch_allgather_fleet(str(tmp_path), n_ranks=2)
    _assert_fleet_ok(res)

    jobs = [
        (tmp_path / f"job_rank{r}.json").read_text() for r in range(2)
    ]
    assert jobs[0] == jobs[1]  # collective: every rank sees the same job

    # in-process reference with the same deterministic per-rank script
    class Clock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

        def advance(self, dt):
            self.t += dt

    results = []
    for rank in range(2):
        clk = Clock()
        mon = TalpMonitor(f"rank{rank}", rank=rank, clock=clk)
        with mon.region("step"):
            clk.advance(1.0 + rank)
            with mon.offload():
                clk.advance(0.5)
        mon.add_device_record(0, DeviceActivity.KERNEL, 0.0,
                              0.25 * (rank + 1))
        results.append(mon.finalize())
    assert jobs[0] == to_json(merge_results(results, name="job"))
