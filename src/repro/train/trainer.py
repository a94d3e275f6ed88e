"""Training step factory and the fused multi-step train window.

``make_train_step(state, batch)`` is the seed per-step path: loss -> grads
-> AdamW, with optional gradient accumulation (microbatching) and
error-feedback int8 gradient compression.  It stays the PARITY ORACLE for
``make_train_window`` — one jitted, state-donating ``lax.scan`` over
``steps_per_sync`` full train steps whose batches are hashed ON DEVICE
(data/pipeline.py::device_batch_at, the bitwise twin of the host pipeline),
so the host only drains stacked loss/grad-norm metrics at window
boundaries.  The window's compiled roofline terms accumulate into dry-run-
shaped records (``train_records``) scored by ``crosslayer.analyze_train``
-> train-mode SRAM/STT/SOT verdicts (DESIGN.md §12).

Gradient compression (``compress_grads=True``) wires the optim/compress.py
error-feedback int8 path in for real: the optimizer is wrapped with
``wrap_optimizer`` (error buffers live in the opt state, so they
checkpoint/reshard/donate with the Adam moments) and — with
``compress_shards > 1`` — per-shard-group gradients combine through
``compressed_psum_ef(..., mean=True)`` under a named data axis, each
shard's quantization residual banked in its OWN error buffer before the
reduce (per-worker EF, the 1-bit-Adam-family schedule; exactly one
quantization per step).  The named-axis collective runs under ``vmap``
over explicit shard groups, so the single-controller jit sees the same
program that shard_map runs per-device on a multi-host data axis.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.data.pipeline import DataConfig, device_batch_at
from repro.models.api import Model
from repro.optim.adamw import AdamW
from repro.optim.compress import wrap_optimizer
from repro.sharding import constrain

TrainState = Dict[str, Any]  # {"params", "opt", "step"}


def effective_optimizer(opt: AdamW, compress_grads: bool = False,
                        compress_shards: int = 1):
    """The optimizer whose state the train step actually carries.

    ``compress_grads=True`` wraps ``opt`` with the error-feedback int8
    compressor (per-shard error buffers when ``compress_shards > 1``);
    build/restore train state with THIS so the state structure matches
    what ``make_train_step``/``make_train_window`` expect.
    """
    return (wrap_optimizer(opt, shards=compress_shards) if compress_grads
            else opt)


def init_state(model: Model, opt, key) -> TrainState:
    params = model.init(key)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_state(model: Model, opt) -> TrainState:
    params = model.abstract_params()
    return {"params": params, "opt": opt.abstract_state(params),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def state_axes(model: Model, opt) -> TrainState:
    axes = model.param_axes()
    return {"params": axes, "opt": opt.state_axes(axes), "step": ()}


def _split_leading(x, n: int):
    return x.reshape((n, x.shape[0] // n) + x.shape[1:])


def window_boundary_crossed(step: int, window: int, every: int) -> bool:
    """True when the window that just ended at ``step`` (i.e. covered
    steps ``step - window .. step``) crossed a multiple of ``every`` —
    the checkpoint cadence shared by launch/train.py and the examples."""
    return (step // every) > ((step - window) // every)


def make_train_step(model: Model, opt: AdamW, *, microbatches: int = 1,
                    compress_grads: bool = False, compress_shards: int = 1,
                    attn_impl: str = "chunked") -> Callable:
    """Build the jittable train step.

    ``microbatches`` grad-accumulates over row chunks of the batch;
    ``compress_grads`` switches the optimizer to the error-feedback int8
    wrapper.  With ``compress_shards > 1`` each shard group microbatch-
    accumulates locally, then the wrapper combines the per-shard
    gradients through ``compressed_psum_ef(..., mean=True)`` on a named
    data axis, banking each shard's residual BEFORE the reduce — the
    distributed error-feedback DP schedule, one quantization per step.
    State must be built with
    ``effective_optimizer(opt, compress_grads, compress_shards)``.
    """
    if microbatches < 1:
        raise ValueError("microbatches must be >= 1")
    if compress_shards < 1:
        raise ValueError("compress_shards must be >= 1")
    if compress_shards > 1 and not compress_grads:
        raise ValueError("compress_shards > 1 requires compress_grads=True")
    opt_eff = effective_optimizer(opt, compress_grads, compress_shards)

    def loss_fn(params, batch):
        return model.loss(params, batch, attn_impl=attn_impl)

    param_axes = model.param_axes()

    def reshard_grads(grads):
        """Pin every grad to its parameter's sharding before the optimizer.

        Without this, backward leaves gradients in whatever (often fully
        gathered) layout the loss used them in, and the elementwise AdamW
        update then runs on gathered f32 moments — measured 147 GiB/device
        on llama3 train_4k under the fsdp strategy (§Perf iteration L2).
        """
        return {k: constrain(g, param_axes[k]) for k, g in grads.items()}

    def local_grads(params, batch):
        """(mean loss, mean grads) over ``microbatches`` chunks of batch."""
        if microbatches == 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        micro = jax.tree.map(lambda x: _split_leading(x, microbatches),
                             batch)

        def acc_body(carry, mb):
            loss_acc, grads_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            return (loss_acc + l,
                    jax.tree.map(jnp.add, grads_acc, g)), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(
            acc_body, (jnp.zeros(()), zeros), micro)
        return (loss / microbatches,
                jax.tree.map(lambda g: g / microbatches, grads))

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        params = state["params"]
        if compress_shards == 1:
            loss, grads = local_grads(params, batch)
            grads = reshard_grads(grads)
        else:
            shards = jax.tree.map(
                lambda x: _split_leading(x, compress_shards), batch)
            # per-shard local grads, stacked on a leading (shards,) axis;
            # the EF int8 combine happens inside the wrapped optimizer
            # (per-shard residuals banked before the reduce)
            loss, grads = jax.vmap(
                lambda mb: local_grads(params, mb))(shards)
            loss = jnp.mean(loss)
            grads = {k: constrain(g, ("batch",) + tuple(param_axes[k]))
                     for k, g in grads.items()}

        new_params, new_opt, metrics = opt_eff.update(
            grads, state["opt"], params)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics, loss=loss)
        return new_state, metrics

    return train_step


class TrainWindow:
    """Fused multi-step training window (the train-side twin of
    serve.Engine's fused decode window, DESIGN.md §12).

    One jitted, state-donating program scans ``steps_per_sync`` (K) full
    train steps; each step hashes its own batch on device from
    ``state["step"]`` (``device_batch_at`` — the bitwise twin of the host
    pipeline), so between host syncs nothing crosses the host boundary and
    the drain is one ``(K,)``-stacked metrics transfer.  The per-step
    oracle (``make_train_step`` + ``data.Pipeline``) consumes the SAME
    token stream, which is what makes loss/metric trajectories directly
    comparable (tests/test_train_engine.py).

    ``record_traffic=True`` lowers+compiles the window a second time and
    runs the §8 roofline HLO walker over it; per-step terms (window / K)
    accumulate into dry-run-shaped records (``train_records``) scored by
    ``core.crosslayer.analyze_train`` -> train-mode SRAM/STT/SOT verdicts
    (``nvm_verdicts``, printed by launch/train.py).
    """

    def __init__(self, model: Model, opt: AdamW, data_cfg: DataConfig, *,
                 steps_per_sync: int, microbatches: int = 1,
                 compress_grads: bool = False, compress_shards: int = 1,
                 attn_impl: str = "chunked", record_traffic: bool = True,
                 state_shardings: Any = None, donate: bool = True):
        if steps_per_sync < 1:
            raise ValueError("steps_per_sync must be >= 1")
        chunks = microbatches * max(compress_shards, 1)
        if data_cfg.host_batch % chunks:
            raise ValueError(
                f"host batch {data_cfg.host_batch} not divisible by "
                f"microbatches x compress_shards = {chunks}")
        self.model = model
        self.opt = effective_optimizer(opt, compress_grads, compress_shards)
        self.data_cfg = data_cfg
        self.steps_per_sync = int(steps_per_sync)
        self.record_traffic = record_traffic
        self._step_fn = make_train_step(
            model, opt, microbatches=microbatches,
            compress_grads=compress_grads, compress_shards=compress_shards,
            attn_impl=attn_impl)

        def window(state: TrainState):
            def body(state, _):
                batch = device_batch_at(data_cfg, state["step"])
                state, metrics = self._step_fn(state, batch)
                return state, {"loss": metrics["loss"],
                               "grad_norm": metrics["grad_norm"],
                               "lr": metrics["lr"]}

            return jax.lax.scan(body, state, None,
                                length=self.steps_per_sync)

        jit_kw: Dict[str, Any] = {}
        if donate:
            jit_kw["donate_argnums"] = (0,)
        if state_shardings is not None:
            jit_kw["in_shardings"] = (state_shardings,)
            jit_kw["out_shardings"] = (state_shardings, None)
        self._window_jit = jax.jit(window, **jit_kw)
        self._traffic = None
        self._analyzed = False   # attempted-once latch: a failed analysis
        self._windows_run = 0    # must not re-lower+compile every window

    # ---- traffic accounting --------------------------------------------
    def _analyze(self, state):
        """Roofline terms of the compiled window.  Failures degrade to
        None (training keeps running) but warn loudly — a silently empty
        ``train_records()`` would erase the NVM-verdict handoff while CI
        stays green."""
        if not self.record_traffic:
            return None
        try:
            from repro.launch import roofline as rf
            return rf.analyze(self._window_jit.lower(state).compile())
        except Exception as e:  # pragma: no cover - backend-dependent
            import warnings
            warnings.warn(
                f"train traffic analysis failed ({e!r}); train_records() "
                "will be empty", RuntimeWarning, stacklevel=2)
            return None

    # ---- engine loop ----------------------------------------------------
    def __call__(self, state: TrainState
                 ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """Run one window: K fused train steps.  Returns (new state,
        stacked ``(K,)`` metrics).  ``state`` is donated — use the
        returned state."""
        if self.record_traffic and not self._analyzed:
            self._traffic = self._analyze(state)
            self._analyzed = True
        state, metrics = self._window_jit(state)
        self._windows_run += 1
        return state, metrics

    # ---- train-mode NVM verdicts ---------------------------------------
    def train_records(self, mesh: Optional[str] = None) -> List[dict]:
        """Dry-run-shaped records of the window's measured traffic: one
        record with PER-STEP roofline terms of the compiled K-step window,
        consumable by ``core.crosslayer.analyze_train`` — the train-mode
        answer to the paper's "would an MRAM tier help THIS workload"
        question, asked of the write-heavy regime where Roy et al. (2023)
        show the STT-MRAM trade-off is sharpest."""
        rl = self._traffic
        if rl is None or not self._windows_run:
            return []
        mesh = mesh or f"{jax.device_count()}dev"
        K = self.steps_per_sync
        cfg = self.data_cfg
        return [{
            "arch": self.model.cfg.arch, "mesh": mesh, "kind": "train",
            "shape": f"train_window_b{cfg.host_batch}_s{cfg.seq_len}_k{K}",
            "steps": self._windows_run * K,
            "roofline": {
                "flops_per_device": rl.flops_per_device / K,
                "bytes_per_device": rl.bytes_per_device / K,
                "collective_bytes": rl.collective_bytes / K,
                "compute_s": rl.compute_s / K,
                "memory_s": rl.memory_s / K,
                "collective_s": rl.collective_s / K,
            }}]

    def nvm_verdicts(self, tier_mb: Optional[float] = None):
        """SRAM/STT/SOT tier verdicts on the window's measured traffic."""
        from repro.core.crosslayer import analyze_train
        kw = {} if tier_mb is None else {"tier_mb": tier_mb}
        return analyze_train(self.train_records(), **kw)


def make_train_window(model: Model, opt: AdamW, *, steps_per_sync: int,
                      microbatches: int = 1, data_cfg: DataConfig,
                      **kw) -> TrainWindow:
    """Build the fused K-step train window (see ``TrainWindow``)."""
    return TrainWindow(model, opt, data_cfg, steps_per_sync=steps_per_sync,
                       microbatches=microbatches, **kw)
