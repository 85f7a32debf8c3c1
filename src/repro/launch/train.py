"""Production training runner: mesh + sharded state + data pipeline +
checkpoint/restart + fault tolerance, end to end.

This is the program a pod job runs.  On this CPU container it runs the
same code path on a (1,1) mesh (or --mesh data,model sizes) with reduced
configs — integration tests and examples drive it that way, which is the
point: one code path from laptop to 512 chips.

    python -m repro.launch.train --arch qwen3-1.7b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Fault-tolerance wiring:
- PreemptionGuard: SIGTERM -> emergency checkpoint -> clean exit (restart
  resumes from it; exercised in tests/test_integration.py).
- Heartbeat file per step (watchdog input).
- StepTimer straggler detection (logged; a fleet supervisor consumes it).
- run_with_restarts: in-process restart controller for crash recovery.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as C
from repro import policy
from repro.configs.reduced import reduced as reduce_cfg
from repro.data import lm_stream, pipeline
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.models import build
from repro.runtime import enable_compile_cache
from repro.train import checkpoint as ckpt_lib
from repro.train import fault_tolerance as ft
from repro.train import grad_compress
from repro.train import optimizer as opt_lib
from repro.train import step as step_lib


def build_runner(cfg, mesh, *, optimizer_name="adamw", lr=3e-4,
                 num_microbatches=1, clip_norm=1.0, total_steps=10000,
                 grad_compressor=None, compress_ratio=0.125):
    model = build(cfg)
    warmup = max(10, min(100, total_steps // 10))
    optimizer = opt_lib.make(optimizer_name,
                             opt_lib.warmup_cosine_lr(lr, warmup,
                                                      total_steps))
    with_residual = grad_compressor is not None
    train_step = step_lib.make_train_step(
        model, optimizer, num_microbatches=num_microbatches,
        clip_norm=clip_norm, grad_compressor=grad_compressor,
        compress_ratio=compress_ratio)
    rules = (shd.MULTI_POD_RULES if "pod" in mesh.axis_names
             else shd.SINGLE_POD_RULES)

    # spec construction under the active mesh: bank_pspec derives its
    # shard grid from the mesh axes (a (1,1) CI mesh shards nothing it
    # can't; an elastic (8,16) restart shards what it can)
    with shd.use_mesh(mesh, rules):
        state_specs = step_lib.state_pspecs(model, optimizer,
                                            with_residual=with_residual)

    def resolve(tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, shd.resolve_spec(s, rules)),
            tree, is_leaf=lambda x: isinstance(x, P))

    state_sh = resolve(state_specs)

    def stepped(state, batch):
        with shd.use_mesh(mesh, rules):
            return train_step(state, batch)

    jitted = jax.jit(stepped, in_shardings=(state_sh, None),
                     out_shardings=(state_sh, None), donate_argnums=(0,))

    def init_state():
        with shd.use_mesh(mesh, rules):
            init = jax.jit(
                lambda k: step_lib.init_state(
                    model, optimizer, k, with_residual=with_residual),
                out_shardings=state_sh)
            return init(jax.random.PRNGKey(0))

    return model, jitted, init_state, state_specs, state_sh


def run(cfg, mesh, *, steps: int, batch: int, seq: int,
        ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
        num_microbatches: int = 1, log_every: int = 10,
        heartbeat_path: Optional[str] = None,
        lr: float = 3e-4, grad_compressor: Optional[str] = None,
        artifact_dir: Optional[str] = None,
        registry_root: Optional[str] = None
        ) -> Dict[str, Any]:
    model, train_step, init_state, state_specs, state_sh = build_runner(
        cfg, mesh, num_microbatches=num_microbatches, lr=lr,
        total_steps=steps, grad_compressor=grad_compressor)

    # the guard covers init/restore too: a preemption signal during the
    # (potentially minutes-long) first compile must not hard-kill the job
    guard_cm = ft.PreemptionGuard()
    guard = guard_cm.__enter__()
    start_step = 0
    state = None
    if ckpt_dir and ckpt_lib.latest_step(ckpt_dir) is not None:
        target = jax.eval_shape(init_state)
        with shd.use_mesh(mesh):
            state = ckpt_lib.restore(
                ckpt_dir, target, mesh=mesh,
                pspecs=jax.tree.map(
                    lambda s: shd.resolve_spec(s), state_specs,
                    is_leaf=lambda x: isinstance(x, P)))
        start_step = int(np.asarray(state["step"]))
        print(f"restored checkpoint @ step {start_step}", flush=True)
    if state is None:
        state = init_state()

    export_hook = None
    if artifact_dir:
        export_hook = ckpt_lib.artifact_exporter(
            cfg, artifact_dir, registry_root=registry_root)

    # a heartbeat needs a home of its own: without ckpt_dir or an explicit
    # path there is none (a shared default would collide across runs)
    if heartbeat_path is None and ckpt_dir:
        heartbeat_path = os.path.join(ckpt_dir, "heartbeat.json")
    hb = ft.Heartbeat(heartbeat_path, host_id=jax.process_index()) \
        if heartbeat_path else None
    timer = ft.StepTimer()
    it = lm_stream.batches(seed=17, batch=batch, seq_len=seq,
                           vocab=cfg.vocab_size,
                           host_id=jax.process_index(),
                           num_hosts=jax.process_count(),
                           start_step=start_step)
    try:
        with shd.use_mesh(mesh):
            data = pipeline.Prefetcher(it, place=lambda b:
                                       pipeline.shard_batch(b, mesh))
            losses = []
            for step_i in range(start_step, steps):
                timer.start()
                batch_arrays = next(data)
                state, metrics = train_step(state, batch_arrays)
                loss = float(np.asarray(metrics["loss"]))
                losses.append(loss)
                t = timer.stop()
                if hb is not None:
                    hb.beat(step_i, loss=loss)
                if log_every and (step_i % log_every == 0):
                    print(f"step {step_i:5d} loss {loss:.4f} "
                          f"({t['step_time']:.2f}s"
                          f"{' STRAGGLER' if t['straggler'] else ''})",
                          flush=True)
                want_ckpt = ckpt_dir and (
                    (step_i + 1) % ckpt_every == 0 or guard.should_stop
                    or step_i + 1 == steps)
                if want_ckpt:
                    ckpt_lib.save(state, ckpt_dir, step_i + 1,
                                  on_save=export_hook)
                if guard.should_stop:
                    print("preemption: emergency checkpoint saved, "
                          "exiting cleanly", flush=True)
                    break
    finally:
        guard_cm.__exit__(None, None, None)
    return {"final_step": int(np.asarray(state["step"])),
            "losses": losses,
            "straggler_count": timer.stragglers}


def main() -> int:
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True, choices=C.names())
    p.add_argument("--reduced", action="store_true",
                   help="CPU-scale variant of the arch (same family)")
    p.add_argument("--hashed", action="store_true",
                   help="enable the paper's hashed weight sharing")
    p.add_argument("--compression", type=float, default=None,
                   help="uniform hashed compression ratio (default 0.125)")
    p.add_argument("--policy", default=None,
                   help="compression policy JSON (per-slot rules; implies "
                        "hashing — see repro.policy)")
    p.add_argument("--budget", default=None,
                   help="equal-memory target: total real params as a "
                        "ratio of dense ('0.125' or '1/8'); solver "
                        "allocates per-slot ratios (implies hashing)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--mesh", default="1,1",
                   help="data,model (or pod,data,model) sizes")
    p.add_argument("--grad-compress", default=None,
                   choices=[None, "hashed_space", "int8"],
                   help="cross-pod gradient compression (error feedback)")
    p.add_argument("--artifact-dir", default=None,
                   help="export a compressed model artifact alongside "
                        "every committed checkpoint")
    p.add_argument("--artifact-quant", default="none",
                   choices=["none", "int8", "fp8"],
                   help="bank quantization for exported artifacts")
    p.add_argument("--registry", default=None,
                   help="model registry root; exported artifacts are "
                        "registered under the config name")
    args = p.parse_args()
    if (args.artifact_quant != "none" or args.registry) \
            and not args.artifact_dir:
        p.error("--artifact-quant/--registry require --artifact-dir")
    if args.artifact_dir and not args.ckpt_dir:
        p.error("--artifact-dir requires --ckpt-dir (artifacts are "
                "exported at checkpoint commits)")

    cfg = C.get(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    if args.policy or args.budget:
        if args.hashed or args.compression is not None:
            p.error("--policy/--budget replace --hashed/--compression "
                    "(pin ratios with a policy rule instead)")
        pol = (policy.load(args.policy) if args.policy
               else policy.CompressionPolicy())
        if args.budget:
            pol = dataclasses.replace(
                pol, budget=policy.parse_ratio(args.budget))
        cfg = cfg.policy_variant(pol)
    elif args.hashed:
        cfg = cfg.hashed_variant(args.compression
                                 if args.compression is not None else 0.125)
    if args.artifact_quant != "none":
        cfg = cfg.with_(artifact_quant=args.artifact_quant)

    sizes = tuple(int(x) for x in args.mesh.split(","))
    axes = ("pod", "data", "model")[-len(sizes):]
    mesh = mesh_lib.make_mesh(sizes, axes)

    out = run(cfg, mesh, steps=args.steps, batch=args.batch, seq=args.seq,
              ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
              num_microbatches=args.microbatches, lr=args.lr,
              grad_compressor=args.grad_compress,
              artifact_dir=args.artifact_dir, registry_root=args.registry)
    print(json.dumps({k: v for k, v in out.items() if k != "losses"}))
    print(f"loss: first={out['losses'][0]:.4f} last={out['losses'][-1]:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
