import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede every other import (jax locks the device
count at first init).  For each cell we:

    with mesh:
        lowered = jax.jit(step, in_shardings=..., out_shardings=...) \
            .lower(*input_specs(...))        # ShapeDtypeStructs only
        compiled = lowered.compile()
        compiled.memory_analysis()           # proves it fits
        compiled.cost_analysis()             # FLOPs/bytes for the roofline

plus a post-SPMD HLO parse that sums per-device collective operand bytes
(all-gather / all-reduce / reduce-scatter / all-to-all / collective-
permute) — cost_analysis does not report them.

Special pseudo-arch ``ecstore``: lowers the MemEC parity delta-update and
decode-from-k reconstruction collectives over the same mesh — the paper's
own technique as a dry-run cell.
"""
import argparse
import json
import re
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.configs.shapes import SHAPES, input_specs, shape_applicable
from repro.distributed import sharding as shd
from repro.distributed.ecstore import ECConfig, ECStateStore
from repro.launch.mesh import make_production_mesh
from repro.models import Model
from repro.train.optimizer import make_optimizer
from repro.train.train_step import make_train_step

# TPU v5e hardware constants (roofline targets; DESIGN.md)
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
                "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b(f64|f32|bf16|f16|f8\w*|s64|u64|s32|u32|s16|u16|"
                       r"s8|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(m) -> int:
    dt = m.group(1)
    base = _DTYPE_BYTES.get(dt[:4] if dt.startswith("f8") else dt, 1)
    dims = m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * base


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))            # [num_groups, group_size]
    m = _GROUPS_V1_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective traffic from the post-SPMD HLO.

    Optimized HLO names operands without inline shapes, so sizes come from
    the RESULT shape + the replica-group size g:
      operand bytes:  all-reduce/all-to-all/permute = result;
                      all-gather = result/g; reduce-scatter = result*g.
      wire bytes (ring model): all-reduce 2(g-1)/g * result;
                      all-gather (g-1)/g * result;
                      reduce-scatter (g-1) * result;
                      all-to-all (g-1)/g * result; permute = result.
    """
    out = {k: 0 for k in _COLLECTIVES}
    wire = {k: 0.0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        lhs, rhs = s.split(" = ", 1)
        opm = re.match(r"(?:\((?:[^()]|\([^)]*\))*\)|\S+)\s+([a-z0-9\-]+)\(",
                       rhs)
        if not opm:
            continue
        op = opm.group(1)
        if op.endswith("-done"):
            continue
        base = op[:-6] if op.endswith("-start") else op
        kind = next((k for k in _COLLECTIVES if base == k), None)
        if kind is None:
            continue
        shapes = list(_SHAPE_RE.finditer(rhs[: rhs.find("(")]))
        if not shapes:
            continue
        result = sum(_shape_bytes(m) for m in shapes)
        g = max(_group_size(s), 1)
        if kind == "all-gather":
            operand = result // g
            w = result * (g - 1) / g
        elif kind == "all-reduce":
            operand = result
            w = 2.0 * result * (g - 1) / g
        elif kind == "reduce-scatter":
            operand = result * g
            w = result * (g - 1)
        elif kind == "all-to-all":
            operand = result
            w = result * (g - 1) / g
        else:  # collective-permute
            operand = result
            w = float(result)
        out[kind] += operand
        wire[kind] += w
        count[kind] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = count
    out["wire"] = {k: int(v) for k, v in wire.items()}
    out["wire_total"] = int(sum(wire.values()))
    return out


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def build_cell(arch: str, shape_name: str, mesh, *, optimizer="adamw8bit",
               remat="full", attn=None, kv=None):
    """Returns (step_fn, args_shapes, in_shardings, out_shardings, meta).
    attn/kv None -> respect the arch config's own setting."""
    from repro.models.layers import set_activation_mesh
    set_activation_mesh(mesh)
    over = {"remat": remat}
    if attn is not None:
        over["attn_parallel"] = attn
    if kv is not None:
        over["kv_cache_dtype"] = kv
    cfg = get_config(arch).scaled(**over)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, why
    model = Model(cfg)
    rng = jax.random.PRNGKey(0)
    params_sh = jax.eval_shape(model.init, rng)
    pspecs = shd.param_specs(cfg, params_sh, mesh)
    batch_sh = input_specs(cfg, shape)
    meta = {"params": int(sum(np.prod(x.shape) for x in
                              jax.tree.leaves(params_sh))),
            "model_params": cfg.param_count(),
            "active_params": cfg.active_param_count()}

    if shape.kind == "train":
        opt = make_optimizer(optimizer, total_steps=10000)
        opt_sh = jax.eval_shape(opt.init, params_sh)
        ospecs = jax.tree.map(
            lambda _: P(), opt_sh,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        # optimizer state shards like its param (moments are per-param)
        ospecs = _opt_specs(opt_sh, pspecs, mesh)
        bspecs = shd.batch_specs(cfg, batch_sh, mesh)
        step = make_train_step(model, opt)
        args = (params_sh, opt_sh, batch_sh)
        in_sh = (pspecs, ospecs, bspecs)
        out_sh = (pspecs, ospecs, jax.tree.map(lambda _: P(), {"loss": 0, "grad_norm": 0}))
        return (step, args, in_sh, out_sh, meta), None

    if shape.kind == "prefill":
        bspecs = shd.batch_specs(cfg, batch_sh, mesh)

        def prefill_step(params, batch):
            return model.apply(params, batch)

        logits_spec = shd.fit_spec(
            P(("pod", "data") if "pod" in mesh.axis_names else ("data",),
              None, "model"),
            (shape.global_batch, shape.seq_len, cfg.padded_vocab), mesh)
        return ((prefill_step, (params_sh, batch_sh), (pspecs, bspecs),
                 logits_spec, meta), None)

    # decode
    cache_sh = jax.eval_shape(
        partial(model.init_cache, shape.global_batch, shape.seq_len,
                dtype=jnp.bfloat16))
    cspecs = shd.cache_specs(cfg, cache_sh, mesh)
    bspecs = shd.batch_specs(cfg, batch_sh, mesh)

    def serve_step(params, cache, batch):
        logits, new_cache = model.decode_step(
            params, cache, batch["tokens"], batch["cur_len"],
            batch.get("positions"))
        return logits, new_cache

    logits_spec = shd.fit_spec(
        P(("pod", "data") if "pod" in mesh.axis_names else ("data",), "model"),
        (shape.global_batch, cfg.padded_vocab), mesh)
    return ((serve_step, (params_sh, cache_sh, batch_sh),
             (pspecs, cspecs, bspecs), (logits_spec, cspecs), meta), None)


def _opt_specs(opt_sh, pspecs, mesh):
    """Optimizer moments inherit their param's spec (quantized int8 moments
    are flat blocks — replicate the tiny scales, shard q like a flat page)."""
    def build(tree):
        if isinstance(tree, dict) and set(tree) == {"q", "s"}:
            return {"q": P(), "s": P()}
        return None

    def rec(o, p=None):
        if isinstance(o, jax.ShapeDtypeStruct):
            if p is not None and len(p) == len(o.shape):
                return shd.fit_spec(p, o.shape, mesh)
            return P()
        if isinstance(o, dict):
            qd = build(o)
            if qd is not None:
                return qd
            out = {}
            for k2, v in o.items():
                pp = p.get(k2) if isinstance(p, dict) and k2 in p else None
                out[k2] = rec(v, pp)
            return out
        if isinstance(o, (list, tuple)):
            t = [rec(v, p[i] if isinstance(p, (list, tuple)) and
                     i < len(p) else None) for i, v in enumerate(o)]
            return type(o)(t)
        return P()

    # moments mirror the params tree under keys m/v/f
    out = {}
    for key, sub in opt_sh.items():
        if key in ("m", "v", "f"):
            out[key] = rec(sub, pspecs)
        else:
            out[key] = jax.tree.map(
                lambda _: P(), sub,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return out


# ---------------------------------------------------------------------------
# the ecstore pseudo-arch (paper-technique cells)
# ---------------------------------------------------------------------------

def build_ec_cell(mesh, *, bytes_per_device: int = 1 << 28, op="update"):
    """Lower the MemEC parity collectives over the mesh.

    bytes_per_device of protected state per chip (default 256 MiB —
    a 123B-param bf16 model sharded over 512 chips is ~0.5 GiB/chip).
    """
    cfg = ECConfig()
    axes = mesh.axis_names
    sizes = dict(zip(axes, mesh.devices.shape))
    pages_local = bytes_per_device // cfg.page_size
    pages_local -= pages_local % cfg.k
    S = pages_local // cfg.k
    gshape = tuple(sizes[a] for a in axes)
    state_sh = jax.ShapeDtypeStruct(
        gshape + (pages_local, cfg.page_size), jnp.uint8)
    par_sh = jax.ShapeDtypeStruct(
        gshape + (cfg.m, S, cfg.page_size), jnp.uint8)
    sspec = P(*axes, None, None)
    pspec = P(*axes, None, None, None)

    from repro.distributed.ecstore import (parity_delta_update,
                                           parity_delta_update_chain,
                                           reconstruct_failed)

    nlead = len(axes)

    if op in ("update", "update_chain"):
        upd = (parity_delta_update_chain if op == "update_chain"
               else parity_delta_update)

        def step(xor_pages, parity):
            def f(xp, par):
                xp = xp.reshape(xp.shape[nlead:])
                par = par.reshape(par.shape[nlead:])
                out = upd(xp, par, cfg)
                return out.reshape((1,) * nlead + out.shape)
            return jax.shard_map(f, mesh=mesh, in_specs=(sspec, pspec),
                                 out_specs=pspec, check_vma=False)(
                                 xor_pages, parity)
        args = (state_sh, par_sh)
        in_sh = (sspec, pspec)
        out_sh = pspec
    else:  # reconstruct
        def step(pages, parity):
            def f(pg, par):
                pg = pg.reshape(pg.shape[nlead:])
                par = par.reshape(par.shape[nlead:])
                rec = reconstruct_failed(pg, par, jnp.int32(3), cfg)
                return rec.reshape((1,) * nlead + rec.shape)
            return jax.shard_map(f, mesh=mesh, in_specs=(sspec, pspec),
                                 out_specs=sspec,
                                 check_vma=False)(pages, parity)
        args = (state_sh, par_sh)
        in_sh = (sspec, pspec)
        out_sh = sspec
    meta = {"bytes_per_device": bytes_per_device, "ec": f"RS({cfg.n},{cfg.k})"}
    return step, args, in_sh, out_sh, meta


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             optimizer="adamw8bit", remat="full", attn=None,
             kv=None, save_hlo: str | None = None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if arch == "ecstore":
        op = shape_name if shape_name in ("update", "update_chain",
                                          "reconstruct") else "update"
        step, args, in_sh, out_sh, meta = build_ec_cell(mesh, op=op)
    else:
        built, why = build_cell(arch, shape_name, mesh,
                                optimizer=optimizer, remat=remat, attn=attn,
                                kv=kv)
        if built is None:
            return {"arch": arch, "shape": shape_name,
                    "mesh": "multi" if multi_pod else "single",
                    "status": "skipped", "reason": why}
        step, args, in_sh, out_sh, meta = built

    def to_named(t):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))

    with mesh:
        jitted = jax.jit(step, in_shardings=to_named(in_sh),
                         out_shardings=to_named(out_sh))
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        from repro.core.analysis import xla_cost_analysis
        cost = xla_cost_analysis(compiled)
    hlo = compiled.as_text()
    # scan-aware analysis (XLA cost_analysis counts while bodies once —
    # see hlo_analysis docstring; raw numbers kept for cross-reference)
    from repro.launch.hlo_analysis import analyze as hlo_analyze
    ana = hlo_analyze(hlo)
    coll = {
        "total": ana["collective_bytes_total"],
        "wire_total": ana["collective_wire_total"],
        "counts": ana["collective_counts"],
        "wire": ana["collective_wire_bytes"],
    }
    for k in _COLLECTIVES:
        coll[k] = ana["collective_op_bytes"].get(k, 0)
        coll["wire"].setdefault(k, 0)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    n_dev = mesh.devices.size
    flops = float(ana["flops"])
    bytes_acc = float(ana["bytes"])
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    mem_d = {}
    if mem is not None:
        for attr in ("generated_code_size_in_bytes",
                     "argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_d[attr] = int(v)
    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "devices": int(n_dev), "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        # per-device numbers (SPMD module), scan-aware (hlo_analysis)
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "xla_cost_analysis_raw": {"flops": raw_flops, "bytes": raw_bytes},
        "collective_bytes_per_device": coll["total"],
        "collective_wire_bytes_per_device": coll["wire_total"],
        "collectives": {k: coll[k] for k in _COLLECTIVES},
        "collective_wire": coll["wire"],
        "collective_counts": coll["counts"],
        "memory_analysis": mem_d,
        "meta": meta,
    }
    # roofline terms (seconds); collective term uses the ring wire model
    res["t_compute"] = flops / PEAK_FLOPS
    res["t_memory"] = bytes_acc / HBM_BW
    res["t_collective"] = coll["wire_total"] / ICI_BW
    terms = {"compute": res["t_compute"], "memory": res["t_memory"],
             "collective": res["t_collective"]}
    res["bottleneck"] = max(terms, key=terms.get)
    if arch != "ecstore":
        model_flops = _model_flops(arch, shape_name)
        res["model_flops_per_device"] = model_flops / n_dev
        res["useful_flops_ratio"] = (
            (model_flops / n_dev) / flops if flops else 0.0)
    return res


def _model_flops(arch: str, shape_name: str) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode: D = global_batch."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if shape.kind == "decode":
        tokens = shape.global_batch  # one token per sequence
        return 2.0 * n_active * tokens  # forward only
    tokens = shape.global_batch * shape.seq_len
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens


def all_cells():
    cells = []
    for arch in ARCH_NAMES:
        for s in SHAPES:
            cells.append((arch, s))
    cells.append(("ecstore", "update"))
    cells.append(("ecstore", "reconstruct"))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--optimizer", default="adamw8bit")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--attn", default=None, choices=["seq", "head", "auto"])
    ap.add_argument("--kv", default=None, choices=["bfloat16", "int8"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = ([(args.arch, args.shape)] if args.arch and args.shape
             else [(a, s) for a, s in all_cells()
                   if (not args.arch or a == args.arch)
                   and (not args.shape or s == args.shape)])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
            if args.tag:
                tag += f"__{args.tag}"
            try:
                res = run_cell(arch, shape, mp, optimizer=args.optimizer,
                               remat=args.remat, attn=args.attn, kv=args.kv,
                               save_hlo=args.save_hlo)
            except Exception as e:  # noqa: BLE001 — record the failure
                res = {"arch": arch, "shape": shape,
                       "mesh": "multi" if mp else "single",
                       "status": "error", "error": f"{type(e).__name__}: {e}"}
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(res, f, indent=1)
            status = res.get("status")
            extra = (res.get("reason") or res.get("error") or
                     f"bottleneck={res.get('bottleneck')} "
                     f"t=({res.get('t_compute', 0):.4f},"
                     f"{res.get('t_memory', 0):.4f},"
                     f"{res.get('t_collective', 0):.4f})s")
            print(f"[{tag}] {status}: {extra}", flush=True)


if __name__ == "__main__":
    main()
