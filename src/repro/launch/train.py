"""Production training launcher.

Builds the largest viable mesh from the available devices (elastic ladder),
derives shardings from the rule engine, restores the latest checkpoint
(resharding onto the current mesh if the fleet changed), and trains with
async checkpointing + straggler monitoring.

Two execution paths share one state layout:
  * fused (default): ``train.trainer.make_train_window`` scans
    ``--steps-per-sync`` (K) full train steps inside one jitted,
    state-donating program, hashing every batch on device — the host only
    drains stacked metrics at window boundaries, where it also checkpoints
    (``CheckpointManager`` at window boundaries, so elastic restore still
    resumes exactly) and prints the window's train-mode NVM verdicts
    (``crosslayer.analyze_train``) at the end;
  * ``--no-fused``: the seed per-step loop (host pipeline batches, one
    dispatch per step) — the parity oracle the fused path is tested
    against.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --steps 100 --reduced          # CPU-sized
On a real TPU fleet drop --reduced; the same code paths run the full
config on the production mesh.  In fused mode the launcher runs whole
windows, so the final step rounds UP to the next multiple of K.
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_context
from repro.data import DataConfig, Pipeline
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.sharding import activation_sharding, default_rules, tree_shardings
from repro.train.checkpoint import CheckpointManager
from repro.train.elastic import StragglerMonitor, choose_mesh, remesh
from repro.train.trainer import (effective_optimizer, init_state,
                                 make_train_step, make_train_window,
                                 state_axes, window_boundary_crossed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--strategy", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="fused K-step train windows (--no-fused for the "
                         "seed per-step oracle loop)")
    ap.add_argument("--steps-per-sync", type=int, default=10,
                    help="fused train steps per host sync (K)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true",
                    help="error-feedback int8 gradient compression "
                         "(optim/compress.py) in the train step")
    ap.add_argument("--compress-shards", type=int, default=1,
                    help="data-parallel shard groups combined through "
                         "compressed_psum (requires --compress-grads)")
    ap.add_argument("--verdicts", action=argparse.BooleanOptionalAction,
                    default=True, help="print train-mode NVM verdicts "
                                       "(fused mode only)")
    args = ap.parse_args()
    enable_compile_cache()

    n = jax.device_count()
    mesh = remesh(n)
    choice = choose_mesh(n)
    print(f"devices={n} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}"
          f" (model_parallelism={choice.model_parallelism})")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg, num_layers=4, d_model=128, d_ff=256)
    model = build_model(cfg, max_seq=args.seq)
    opt = AdamW(lr=warmup_cosine(args.lr, 10, args.steps))
    opt_eff = effective_optimizer(opt, args.compress_grads,
                                  args.compress_shards)
    rules = default_rules(fsdp=cfg.fsdp, multi_pod=(len(mesh.shape) == 3),
                          strategy=args.strategy)
    dcfg = DataConfig(cfg.vocab_size, args.seq, args.batch,
                      num_hosts=jax.process_count(),
                      host_id=jax.process_index())

    with mesh_context(mesh), activation_sharding(mesh, rules):
        state = init_state(model, opt_eff, jax.random.PRNGKey(0))
        st_sh = tree_shardings(state_axes(model, opt_eff), state, mesh,
                               rules)
        state = jax.tree.map(jax.device_put, state, st_sh)

        mgr = CheckpointManager(args.ckpt_dir, keep=2)
        start = 0
        if mgr.latest_step() is not None:
            # elastic restart: reshards onto whatever mesh we built above
            state = mgr.restore(state, shardings=st_sh)
            start = int(mgr.latest_step())
            print(f"restored step {start} (resharded onto current mesh)")

        mon = StragglerMonitor(num_hosts=jax.process_count())
        if args.fused:
            win = _run_fused(args, model, opt, dcfg, st_sh, state, mgr, mon,
                             start)
            if args.verdicts and win is not None:
                for v in win.nvm_verdicts():
                    print(f"  {v.shape}: energy vs SRAM "
                          f"STT {v.energy_ratio['STT']:.3f} / "
                          f"SOT {v.energy_ratio['SOT']:.3f}   EDP "
                          f"STT {v.edp_ratio['STT']:.3f} / "
                          f"SOT {v.edp_ratio['SOT']:.3f}")
        else:
            _run_per_step(args, model, opt, dcfg, st_sh, state, mgr, mon,
                          start)


def _run_fused(args, model, opt, dcfg, st_sh, state, mgr, mon, start):
    """Window loop: K fused steps per host sync; checkpoint + straggler
    accounting at window boundaries.  Returns the window (for verdicts),
    or None if the restored step already covers ``--steps``."""
    K = args.steps_per_sync
    if start >= args.steps:
        print(f"restored step {start} >= --steps {args.steps}; nothing to "
              f"do (checkpoints {mgr.all_steps()})")
        return None
    win = make_train_window(
        model, opt, steps_per_sync=K, microbatches=args.microbatches,
        compress_grads=args.compress_grads,
        compress_shards=args.compress_shards, data_cfg=dcfg,
        state_shardings=st_sh)
    last_loss = None
    t0 = time.time()
    step = start
    while step < args.steps:
        state, metrics = win(state)
        # drain: ONE host transfer of the stacked (K,) metrics; blocking
        # here also makes the recorded time device time, not dispatch time
        losses = np.asarray(metrics["loss"])
        step += K
        mon.record(jax.process_index(), (time.time() - t0) / K)
        t0 = time.time()
        flagged = mon.stragglers()
        if flagged:
            print(f"straggler(s) {flagged}: would trigger evict+remesh "
                  f"(see train/elastic.py)")
        if window_boundary_crossed(step, K, args.ckpt_every) \
                or step >= args.steps:
            mgr.save(step, state, blocking=(step >= args.steps))
        last_loss = float(losses[-1])
        print(f"step {step:4d} loss {last_loss:.4f} "
              f"(window mean {float(losses.mean()):.4f})")
    print(f"done @{step}: loss {last_loss:.4f}; "
          f"checkpoints {mgr.all_steps()}")
    return win


def _run_per_step(args, model, opt, dcfg, st_sh, state, mgr, mon, start):
    """The seed per-step oracle loop (host pipeline, one dispatch/step)."""
    step_fn = jax.jit(
        make_train_step(model, opt, microbatches=args.microbatches,
                        compress_grads=args.compress_grads,
                        compress_shards=args.compress_shards),
        in_shardings=(st_sh, None), out_shardings=(st_sh, None),
        donate_argnums=(0,))
    data = Pipeline(dcfg, start_step=start)
    t0 = time.time()
    metrics = {}
    for i, batch in zip(range(start, args.steps), data):
        state, metrics = step_fn(state, jax.tree.map(np.asarray, batch))
        # block before timing: otherwise we record async dispatch time,
        # not device step time, and the straggler monitor sees noise
        jax.block_until_ready(metrics)
        mon.record(jax.process_index(), time.time() - t0)
        t0 = time.time()
        flagged = mon.stragglers()   # mutates strikes: call ONCE per step
        if flagged:
            print(f"straggler(s) {flagged}: would trigger evict+remesh "
                  f"(see train/elastic.py)")
        if (i + 1) % args.ckpt_every == 0:
            mgr.save(i + 1, state)
        if (i + 1) % 10 == 0:
            print(f"step {i+1:4d} loss {float(metrics['loss']):.4f}")
    mgr.save(max(args.steps, start), state, blocking=True)
    data.close()
    # restoring at/after the final step leaves the loop body unentered and
    # metrics empty — the seed's closing float(metrics['loss']) raised
    tail = (f"loss {float(metrics['loss']):.4f}; " if metrics else
            f"restored step {start} >= --steps {args.steps}, no steps run; ")
    print(f"done @{max(args.steps, start)}: {tail}"
          f"checkpoints {mgr.all_steps()}")


if __name__ == "__main__":
    main()
