"""End-to-end training driver with TALP monitoring as a first-class
feature.

Every step runs under TALP regions/states:
  * host *Useful*  — data synthesis + python control,
  * *Offload*      — device dispatch + blocked-on-device time (with a
                     device Kernel record via the runtime backend),
  * *MPI*          — cross-process control-plane waits (checkpoint
                     barrier in multi-process runs; ~0 single-process),
and the paper's text/JSON report is emitted at exit and, with
``--talp-sample-every``, every N steps (TALP's online mode; the TALP
options are described in :mod:`repro.launch.session`). The batch and the
dispatch (``train.batch``, ``train.dispatch``) and TALP's own scopes are
profiler spans, on the device trace's clock. Checkpoint/restart and
straggler detection are integrated (fault tolerance), and the data
pipeline prefetches in the background.

Usage (CPU-sized):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..configs import ShapeConfig, get_config, list_configs, smoke_config
from ..core.backends import RuntimeBackend
from ..core.telemetry import spans
from ..data.pipeline import DataConfig, SyntheticTokenPipeline
from ..optim.adamw import AdamWConfig
from ..runtime.fault_tolerance import StragglerDetector
from .compile_cache import enable_compile_cache
from .session import TalpSession, add_arguments, options_from
from .steps import init_train_state, make_train_step, train_state_shapes

__all__ = ["train", "parser", "main"]


def train(
    cfg,
    steps: int = 50,
    global_batch: int = 8,
    seq_len: int = 128,
    ckpt_dir: str = None,
    ckpt_every: int = 20,
    opt_cfg: AdamWConfig = None,
    fail_at_step: int = None,   # failure injection (tests)
    seed: int = 0,
    verbose: bool = True,
    **talp,
):
    """Train a (usually reduced) config; returns (state, history, talp).

    ``talp`` holds the TALP options of
    :class:`~repro.launch.session.TalpSession` (``rank``,
    ``world_size``, ``talp_*``). The step region is ``step``, and the
    sample interval counts steps.
    """
    opt_cfg = opt_cfg or AdamWConfig(warmup_steps=10, total_steps=steps)
    backend = RuntimeBackend()
    session = TalpSession(
        "train", step_region="step", backend=backend, verbose=verbose,
        flop=(cfg, ShapeConfig(name="train", seq_len=seq_len,
                               global_batch=global_batch, kind="train")),
        **talp)
    mon = session.monitor

    data = SyntheticTokenPipeline(
        DataConfig(
            global_batch=global_batch,
            seq_len=seq_len,
            vocab_size=cfg.vocab_size,
            embed_dim=cfg.d_model if cfg.frontend == "embed" else 0,
            seed=seed,
        ),
        process_index=session.rank,
        process_count=session.world_size,
    )

    step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=0)
    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    detector = StragglerDetector()

    # --- init or resume ---------------------------------------------------
    start_step = 0
    state = None
    if manager is not None:
        state, start_step = manager.restore_latest(train_state_shapes(cfg))
    if state is None:
        with mon.region("init"):
            state = init_train_state(cfg, jax.random.PRNGKey(seed))
            state = jax.block_until_ready(state)
        start_step = 0

    history = []
    with mon.region("train_loop"):
        for step in range(start_step, steps):
            t0 = time.perf_counter()
            if fail_at_step is not None and step == fail_at_step:
                raise RuntimeError(f"injected failure at step {step}")
            with session.step():
                # host Useful: data synthesis (prefetch keeps this short)
                with spans.span("train.batch"):
                    batch = data.batch_at(step)
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                # Offload: dispatch + block (async launch → kernel record)
                with spans.span("train.dispatch"):
                    handle = backend.launch(step_fn, state, batch,
                                            name="train_step")
                with mon.offload():
                    state, metrics = backend.wait(handle)
                if manager is not None and (step + 1) % ckpt_every == 0:
                    # snapshot is sync (short), file write is async
                    with mon.mpi():   # control-plane barrier analogue
                        manager.save(step, state)
            dt = time.perf_counter() - t0
            detector.observe(step, dt)
            history.append(
                {"step": step, "loss": float(metrics["loss"]),
                 "grad_norm": float(metrics["grad_norm"]), "time_s": dt}
            )
            session.tick(step)
            if verbose and (step % 10 == 0 or step == steps - 1):
                print(f"step {step:5d} loss {history[-1]['loss']:.4f} "
                      f"({dt*1e3:.0f} ms)")
                sys.stdout.flush()

    if manager is not None:
        manager.save(steps - 1, state)
        manager.wait()
    data.stop()
    result = session.finish()
    if verbose and detector.events:
        print(f"straggler events at steps: {detector.events}")
    return state, history, result


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_configs(), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    add_arguments(ap)
    ap.add_argument("--history-json", default=None)
    return ap


def main():
    args = parser().parse_args()
    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    _, history, _ = train(
        cfg,
        steps=args.steps,
        global_batch=args.batch,
        seq_len=args.seq,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        **options_from(args),
    )
    if args.history_json:
        with open(args.history_json, "w") as f:
            json.dump(history, f)
    losses = [h["loss"] for h in history]
    if losses and not (np.isfinite(losses[-1]) and losses[-1] < losses[0]):
        print("WARNING: loss did not decrease")


if __name__ == "__main__":
    main()
