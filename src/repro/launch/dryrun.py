import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers, compiles,
fits, and report its roofline terms — without TPU hardware.

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder devices. (Smoke tests
and benches must NOT import this module — they see 1 device.)

Per cell this driver does two compiles:
  1. full-depth **scanned** model: lower + compile on the production mesh —
     proves the sharding is coherent (no mismatch, no unsupported collective)
     and yields ``memory_analysis()`` (true per-device footprint).
  2. 1- and 2-superblock **unrolled** variants: ``cost_analysis()`` +
     HLO-text collective bytes, linearly extrapolated to full depth
     (cost_analysis does not multiply through ``while`` loops — verified).

Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>.json, consumed by
EXPERIMENTS.md §Dry-run/§Roofline and benchmarks/roofline_table.py.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import dataclasses
import functools
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import sharding as shd
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim.optimizers import adamw, apply_updates
from repro.optim.grad_utils import clip_by_global_norm
from repro.roofline import analysis as roof
from repro.roofline import traffic

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _art_path(arch: str, shape: str, mesh_name: str, tag: str = "") -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    return os.path.join(ART_DIR, f"{arch}__{shape}__{mesh_name}{suffix}.json")


# ---------------- step functions ----------------

def make_train_fn(cfg: ModelConfig, num_groups: int):
    opt = adamw(lr=3e-4)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch, num_groups))(params)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss, "gnorm": gnorm}

    return opt, train_step


def lower_cell(cfg: ModelConfig, kind: str, seq: int, batch: int, mesh,
               num_groups: int):
    """Lower + compile one cell on ``mesh``; returns (compiled, lowered, s)."""
    from repro.models import transformer as tf
    from repro.models.actsharding import set_act_mesh

    set_act_mesh(mesh)
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda: M.init_params(cfg, key))
    pspecs = shd.param_specs(params_shape, mesh, cfg)
    p_shard = shd.to_named(pspecs, mesh)

    ispec = configs.input_specs(cfg, _shape_for(kind), batch=batch, seq=seq)
    bspecs = shd.batch_specs(ispec["batch"], mesh)
    b_shard = shd.to_named(bspecs, mesh)

    if kind == "train":
        opt, train_step = make_train_fn(cfg, num_groups)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        o_shard = _opt_shardings(opt_shape, pspecs, mesh)
        fn = jax.jit(train_step,
                     in_shardings=(p_shard, o_shard, b_shard),
                     donate_argnums=(0, 1))
        lowered = fn.lower(params_shape, opt_shape, ispec["batch"])
    elif kind == "prefill":
        def prefill_fn(params, batch):
            return M.prefill(cfg, params, batch, max_len=seq,
                             num_groups=num_groups)

        caches_shape = jax.eval_shape(
            lambda: tf.init_caches(cfg, batch, seq, jnp.dtype(cfg.dtype)))
        cspecs = shd.cache_specs(caches_shape, mesh, cfg)
        fn = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard),
                     out_shardings=(
                         jax.sharding.NamedSharding(
                             mesh, shd.logits_spec(cfg, mesh, batch)),
                         shd.to_named(cspecs, mesh)))
        lowered = fn.lower(params_shape, ispec["batch"])
    else:  # decode — serve-mode params: bf16, TP-only (no per-token FSDP
        # all-gathers; inference keeps no optimizer state, so replicating
        # over `data` costs only params/TP bytes — fits every arch in bf16)
        from repro.models.actsharding import set_weight_constrain
        set_weight_constrain(False)
        cfg_srv = dataclasses.replace(cfg, param_dtype="bfloat16")
        params_shape = jax.eval_shape(
            lambda: M.init_params(cfg_srv, key))
        p_shard = shd.to_named(
            shd.param_specs(params_shape, mesh, cfg_srv, serve_mode=True),
            mesh)
        caches_shape = jax.eval_shape(
            lambda: tf.init_caches(cfg, batch, seq, jnp.dtype(cfg.dtype)))
        cspecs = shd.cache_specs(caches_shape, mesh, cfg)
        c_shard = shd.to_named(cspecs, mesh)

        def serve_fn(params, token, pos, caches):
            return M.serve_step(cfg_srv, params, token, pos, caches,
                                num_groups=num_groups)

        fn = jax.jit(
            serve_fn,
            in_shardings=(p_shard, b_shard["token"], b_shard["pos"],
                          c_shard),
            out_shardings=(jax.sharding.NamedSharding(
                mesh, shd.logits_spec(cfg, mesh, batch)), c_shard),
            donate_argnums=(3,))
        lowered = fn.lower(params_shape, ispec["batch"]["token"],
                           ispec["batch"]["pos"], caches_shape)
    t0 = time.time()
    compiled = lowered.compile()
    return compiled, lowered, time.time() - t0


def _shape_for(kind: str) -> str:
    return {"train": "train_4k", "prefill": "prefill_32k",
            "decode": "decode_32k"}[kind]


def _opt_shardings(opt_shape, pspecs, mesh):
    """Optimizer state shares param specs; scalars replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def match(o):
        # AdamState(count, mu, nu): mu/nu mirror params
        return type(o)(NamedSharding(mesh, P()),
                       shd.to_named(pspecs, mesh),
                       shd.to_named(pspecs, mesh))

    return match(opt_shape)


# ---------------- per-cell analysis ----------------

def _cost_dict(compiled) -> Dict[str, float]:
    ca = roof.cost_dict(compiled.cost_analysis())
    return {"flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes": float(ca.get("bytes accessed", 0.0) or 0.0)}


def run_cell(arch: str, shape: str, multi_pod: bool,
             save: bool = True, tag: str = "",
             cfg_override=None, mesh_shape=None) -> Dict[str, Any]:
    """``mesh_shape=(data, model)`` remaps the SAME 256 chips/pod to a
    different logical (data, model) split — the TP-degree tuning knob used in
    §Perf (small models want wide data axes, not 16-way TP)."""
    if mesh_shape is not None:
        d, m = mesh_shape
        if multi_pod:
            mesh = make_mesh((2, d, m), ("pod", "data", "model"))
            mesh_name = f"pod2x{d}x{m}"
        else:
            mesh = make_mesh((d, m), ("data", "model"))
            mesh_name = f"pod{d}x{m}"
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = int(mesh.devices.size)
    cfg = cfg_override or configs.get_config(arch)
    ok, why = configs.applicable(cfg, shape)
    if not ok:
        art = {"arch": arch, "shape": shape, "mesh": mesh_name,
               "status": "skipped", "reason": why}
        if save:
            with open(_art_path(arch, shape, mesh_name, tag), "w") as f:
                json.dump(art, f, indent=1)
        return art

    info = configs.SHAPES[shape]
    kind, seq, batch = info["kind"], info["seq"], info["batch"]
    num_groups = shd.axis_size(mesh, shd.batch_axes(mesh))
    if batch % num_groups != 0:
        num_groups = 1

    t_all = time.time()
    # 1) full-depth scanned compile: shardability + memory
    compiled, lowered, t_compile = lower_cell(cfg, kind, seq, batch, mesh,
                                              num_groups)
    mem = compiled.memory_analysis()
    # per-device (verified): arguments = params+opt+batch shard; temp on the
    # CPU backend is a no-liveness upper bound (sum of all HLO values) — we
    # record both and treat argument+output as the residency floor.
    mem_info = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes_upper": getattr(mem, "temp_size_in_bytes", None),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        "resident_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
        + (getattr(mem, "output_size_in_bytes", 0) or 0),
    }

    # 2) cost extrapolation from unrolled 1- and 2-superblock variants
    pattern = len(cfg.superblock())
    costs, colls = [], []
    for nsb in (1, 2):
        cfg_n = dataclasses.replace(cfg, num_layers=pattern * nsb,
                                    scan_layers=False,
                                    enc_layers=min(cfg.enc_layers, nsb)
                                    if cfg.enc_layers else 0)
        comp_n, low_n, _ = lower_cell(cfg_n, kind, seq, batch, mesh,
                                      num_groups)
        costs.append(_cost_dict(comp_n))
        cb = roof.collective_bytes(comp_n.as_text())
        colls.append(cb)
    nsb_full = cfg.num_superblocks
    cost_full = roof.extrapolate(costs[0], costs[1], nsb_full)
    coll_full = roof.extrapolate(
        {k: v for k, v in colls[0].items() if k != "_counts"},
        {k: v for k, v in colls[1].items() if k != "_counts"}, nsb_full)
    # encoder stack (seamless) scales with its own depth; the nsb=1/2 pair
    # uses enc_layers=1/2 so the same linear extrapolation covers it.

    total_coll = float(sum(coll_full.values()))
    mesh_shape = dict(mesh.shape)
    traffic_model = traffic.analytic_bytes(cfg, kind, seq, batch, mesh_shape)
    rl = roof.Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=cost_full["flops"], hlo_bytes=traffic_model["total"],
        coll_bytes=total_coll, coll_by_op=coll_full,
        model_flops=roof.model_flops_for(cfg, kind, seq, batch),
        per_device_mem=mem_info["resident_bytes"])

    art = {"status": "ok", "kind": kind, "seq": seq, "global_batch": batch,
           "compile_seconds": t_compile,
           "total_seconds": time.time() - t_all,
           "memory": mem_info,
           "hlo_bytes_raw": cost_full["bytes"],  # CPU-backend upper bound
           "traffic_breakdown": traffic_model,
           "collective_counts_nsb2": colls[1].get("_counts"),
           **rl.to_dict()}
    if save:
        with open(_art_path(arch, shape, mesh_name, tag), "w") as f:
            json.dump(art, f, indent=1)
    return art


# ---------------- CLI ----------------

def _run_all(multi_pod: bool, skip_existing: bool, tag: str = ""):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    results = []
    for arch in configs.list_archs():
        for shape in configs.SHAPE_NAMES:
            path = _art_path(arch, shape, mesh_name, tag)
            if skip_existing and os.path.exists(path):
                print(f"[skip existing] {arch} {shape}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape]
            if tag:
                cmd += ["--tag", tag]
            if multi_pod:
                cmd.append("--multi-pod")
            print(f"[run] {arch} {shape} {mesh_name}", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=3600,
                               env={**os.environ,
                                    "PYTHONPATH": os.environ.get(
                                        "PYTHONPATH", "src")})
            if r.returncode != 0:
                print(r.stdout[-2000:])
                print(r.stderr[-4000:])
                results.append((arch, shape, "FAIL"))
            else:
                results.append((arch, shape, "ok"))
    print("\n=== dry-run summary ===")
    for a, s, st in results:
        print(f"{a:26s} {s:12s} {st}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=configs.SHAPE_NAMES)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    if args.all:
        _run_all(args.multi_pod, args.skip_existing, args.tag)
        return
    assert args.arch and args.shape, "--arch and --shape (or --all)"
    art = run_cell(args.arch, args.shape, args.multi_pod, tag=args.tag)
    if art["status"] == "skipped":
        print(f"SKIPPED: {art['reason']}")
        return
    print(json.dumps({k: v for k, v in art.items()
                      if k not in ("coll_by_op",)}, indent=1, default=str))
    print(f"resident per device: "
          f"{art['memory']['resident_bytes']/2**30:.2f} GiB")
    print(f"t_compute={art['t_compute']:.4e}s t_memory={art['t_memory']:.4e}s"
          f" t_collective={art['t_collective']:.4e}s ->"
          f" bottleneck={art['bottleneck']}")


if __name__ == "__main__":
    main()
