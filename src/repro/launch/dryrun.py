import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first backend init).  Everything else follows.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

(No `from __future__ import annotations` here: the XLA_FLAGS lines must stay
the first statements in the file, which a __future__ import forbids.)

For each runnable cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds ShapeDtypeStruct stand-ins for params/opt/batch/cache (no
     allocation),
  3. jit(step, in_shardings, out_shardings).lower(...).compile(),
  4. records memory_analysis() (proves per-device fit), cost_analysis()
     (FLOPs/bytes for §Roofline) and the collective-op byte census parsed
     from the compiled HLO (collective term for §Roofline).

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
  python -m repro.launch.dryrun --all --mesh single --no-sp --out results/ablate
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch import sharding as shard_mod
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.optim import OptimizerConfig
from repro.training import (TrainConfig, make_decode_step, make_prefill_step,
                            make_train_step)

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# `%name = <shape> <op>(...)`: the scheduled HLO prints operand NAMES without
# shapes, so the census keys off each collective's RESULT shape and converts
# to operand bytes with the per-op relation (all-gather result = operand *
# group, reduce-scatter result = operand / group, others 1:1).
_INSTR_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:\w+\[[\d,]*\][^\s]*))\s+([\w-]+)\(")
_SHAPE_PART_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _nbytes(dtype: str, dims: str) -> int:
    n = _DTYPE_BYTES.get(dtype, 4)
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _result_bytes(shape_str: str) -> int:
    return sum(_nbytes(d, s) for d, s in _SHAPE_PART_RE.findall(shape_str))


def _group_size(line: str, n_devices: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))           # [n_groups, group_size]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([t for t in m.group(1).split(",") if t.strip() != ""])
    return n_devices


def collective_census(hlo_text: str, n_devices: int = 1) -> dict:
    """Per-device byte census of every collective op in the compiled HLO.

    Records, per op kind: instruction count, summed operand bytes, and
    summed *link* bytes (ring cost (g-1)/g per device — what the collective
    roofline term divides by link bandwidth).
    """
    base_ops = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
    census: dict[str, dict] = {op: {"count": 0, "operand_bytes": 0,
                                    "link_bytes": 0} for op in base_ops}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shape_str, opname = m.group(1), m.group(2)
        root = opname
        for suffix in ("-start", "-done"):
            if root.endswith(suffix):
                root = root[: -len(suffix)]
        if root not in census or opname.endswith("-done"):
            continue
        rb = _result_bytes(shape_str)
        g = max(_group_size(line, n_devices), 1)
        if root == "all-gather":
            operand = rb // max(g, 1)
            link = operand * (g - 1)          # ring all-gather per device
        elif root == "reduce-scatter":
            operand = rb * g
            link = rb * (g - 1)
        elif root == "all-reduce":
            operand = rb
            link = 2 * rb * (g - 1) // max(g, 1)   # RS + AG ring
        elif root == "all-to-all":
            operand = rb
            link = rb * (g - 1) // max(g, 1)
        else:  # collective-permute
            operand = rb
            link = rb
        census[root]["count"] += 1
        census[root]["operand_bytes"] += operand
        census[root]["link_bytes"] += link
    census["total_bytes"] = sum(v["operand_bytes"] for v in census.values()
                                if isinstance(v, dict))
    census["total_link_bytes"] = sum(v["link_bytes"]
                                     for v in census.values()
                                     if isinstance(v, dict))
    return census


def build_cell(arch: str, shape_name: str, mesh, *, seq_parallel: bool = False,
               opt_overrides: dict | None = None, cfg_overrides: dict | None = None,
               train_overrides: dict | None = None):
    """Returns (step_fn, in_args, in_shardings, out_shardings) for the cell."""
    cfg = get_config(arch)
    # Unrolled stacks by default: exact HLO cost accounting for §Roofline
    # (HloCostAnalysis counts while-loop bodies once; see ModelConfig).
    overrides = {"unroll_layers": True}
    overrides.update(cfg_overrides or {})
    cfg = dataclasses.replace(cfg, **overrides)
    cell = specs_mod.SHAPES[shape_name]
    rules = shard_mod.rules_for(arch, mesh, seq_parallel=seq_parallel)
    params_shapes, param_shard = specs_mod.abstract_params(cfg, mesh, rules)

    if cell.kind == "train":
        opt_cfg = OptimizerConfig(**(opt_overrides or {}))
        opt_shapes, opt_shard = specs_mod.abstract_opt_state(
            opt_cfg, params_shapes, param_shard, mesh)
        batch_tree, batch_shard = specs_mod.token_specs(
            cfg, cell.batch, cell.seq, mesh)
        raw_step = make_train_step(cfg, opt_cfg,
                                   TrainConfig(**(train_overrides or {})))

        def step(params, opt_state, batch):
            with shard_mod.use_rules(mesh, rules):
                return raw_step(params, opt_state, batch)

        in_args = (params_shapes, opt_shapes, batch_tree)
        in_shard = (param_shard, opt_shard, batch_shard)
        rep = NamedSharding(mesh, P())
        out_shard = (param_shard, opt_shard, None)
        return step, in_args, in_shard, out_shard, cfg

    if cell.kind == "prefill":
        batch_tree, batch_shard = specs_mod.token_specs(
            cfg, cell.batch, cell.seq, mesh)
        raw_step = make_prefill_step(cfg, max_len=cell.seq)

        def step(params, tokens):
            with shard_mod.use_rules(mesh, rules):
                return raw_step(params, tokens)

        in_args = (params_shapes, batch_tree["inputs"])
        in_shard = (param_shard, batch_shard["inputs"])
        cache_shapes = specs_mod.abstract_cache(cfg, cell.batch, cell.seq,
                                                params_shapes)
        cache_shard = specs_mod.cache_shardings(cfg, cache_shapes, mesh,
                                                cell.batch)
        out_shard = (None, cache_shard)
        return step, in_args, in_shard, out_shard, cfg

    # decode
    raw_step = make_decode_step(cfg)
    cache_shapes = specs_mod.abstract_cache(cfg, cell.batch, cell.seq,
                                            params_shapes)
    cache_shard = specs_mod.cache_shardings(cfg, cache_shapes, mesh,
                                            cell.batch)
    bspec = specs_mod.batch_spec(mesh)
    token = jax.ShapeDtypeStruct((cell.batch, 1), jnp.int32)
    token_shard = NamedSharding(
        mesh, P(*bspec, None) if cell.batch > 1 else P(None, None))

    def step(params, cache, token):
        with shard_mod.use_rules(mesh, rules):
            return raw_step(params, cache, token)

    in_args = (params_shapes, cache_shapes, token)
    in_shard = (param_shard, cache_shard, token_shard)
    out_shard = (None, cache_shard)
    return step, in_args, in_shard, out_shard, cfg


def _pattern_period(cfg) -> int:
    return max(cfg.global_every, cfg.shared_attn_every, 1)


def _compile_once(arch, shape_name, mesh, *, seq_parallel, opt_overrides,
                  cfg_overrides, train_overrides=None, save_hlo=None,
                  top_colls=0):
    step, in_args, in_shard, out_shard, cfg = build_cell(
        arch, shape_name, mesh, seq_parallel=seq_parallel,
        opt_overrides=opt_overrides, cfg_overrides=cfg_overrides,
        train_overrides=train_overrides)
    with mesh:
        lowered = jax.jit(step, in_shardings=in_shard,
                          out_shardings=out_shard).lower(*in_args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    census = collective_census(hlo, n_devices=mesh.size)
    if top_colls:
        census["top"] = top_collectives(hlo, mesh.size, top_colls)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    del hlo
    return {
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_bytes": (mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      - mem.alias_size_in_bytes),
        },
        "cost": {
            "flops_per_device": cost.get("flops", 0.0),
            "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
        },
        "collectives": census,
    }


def top_collectives(hlo_text: str, n_devices: int, k: int = 10) -> list:
    """Largest collective instructions (forensics for §Perf)."""
    rows = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shape_str, opname = m.group(1), m.group(2)
        root = opname.removesuffix("-start").removesuffix("-done")
        if root not in ("all-reduce", "all-gather", "reduce-scatter",
                        "all-to-all", "collective-permute") or \
                opname.endswith("-done"):
            continue
        rb = _result_bytes(shape_str)
        name = re.search(r'op_name="([^"]*)"', line)
        rows.append({"op": root, "result_bytes": rb,
                     "group": _group_size(line, n_devices),
                     "shape": shape_str[:60],
                     "origin": (name.group(1)[-90:] if name else "")})
    rows.sort(key=lambda r: -r["result_bytes"])
    return rows[:k]


def _lin_combine(c1, c2, l1, l2, total_layers):
    """Linear reconstruction: full-depth cost from two shallow compiles."""
    scale = (total_layers - l1) / max(l2 - l1, 1)

    def rec(a, b):
        if isinstance(a, dict):
            return {k: rec(a[k], b[k]) for k in a if k in b}
        if isinstance(a, (int, float)):
            return a + scale * (b - a)
        return a

    return rec(c1, c2)


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             seq_parallel: bool = False, opt_overrides=None,
             cfg_overrides=None, train_overrides=None,
             save_hlo: str | None = None,
             cost_pass: bool | None = None) -> dict:
    """One dry-run cell = up to two compile passes.

    1. scan-over-layers at full depth: the compile-success proof + the
       per-device memory_analysis (correct buffer liveness).
    2. (single-pod default) python-unrolled at depths (p, 2p) where p is the
       layer-pattern period: HloCostAnalysis counts while bodies once, so
       flops/bytes/collectives are reconstructed linearly from the two
       shallow unrolled compiles — exact for homogeneous stacks.
    """
    cfg = get_config(arch)
    ok, reason = specs_mod.cell_applicable(cfg, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    base = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "seq_parallel": seq_parallel}
    if not ok:
        return dict(base, status="skipped", reason=reason)
    if specs_mod.SHAPES[shape_name].kind == "decode":
        seq_parallel = False        # decode activations have seq = 1
        base["seq_parallel"] = False
    if cost_pass is None:
        cost_pass = not multi_pod

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        over = dict(cfg_overrides or {})
        over["unroll_layers"] = False
        full = _compile_once(arch, shape_name, mesh, seq_parallel=seq_parallel,
                             opt_overrides=opt_overrides, cfg_overrides=over,
                             train_overrides=train_overrides,
                             save_hlo=save_hlo)
        result = dict(
            base, status="ok", n_devices=mesh.size,
            memory=full["memory"],
            scan_cost=full["cost"],          # loop bodies counted once
            model={"n_params": cfg.n_params(),
                   "n_active_params": cfg.n_active_params()},
        )
        if cost_pass:
            p = _pattern_period(cfg)
            l1, l2 = p, 2 * p
            shallow = []
            for ll in (l1, l2):
                o = dict(cfg_overrides or {})
                o.update(unroll_layers=True, num_layers=ll)
                shallow.append(_compile_once(
                    arch, shape_name, mesh, seq_parallel=seq_parallel,
                    opt_overrides=opt_overrides, cfg_overrides=o,
                    train_overrides=train_overrides,
                    top_colls=10 if ll == l2 else 0))
            cost = _lin_combine(shallow[0]["cost"], shallow[1]["cost"],
                                l1, l2, cfg.num_layers)
            colls = _lin_combine(
                {k: v for k, v in shallow[0]["collectives"].items()
                 if k != "top"},
                {k: v for k, v in shallow[1]["collectives"].items()
                 if k != "top"},
                l1, l2, cfg.num_layers)
            colls["top"] = shallow[1]["collectives"].get("top", [])
            result["cost"] = cost
            result["collectives"] = colls
            result["cost_calibration"] = {"l1": l1, "l2": l2}
        result["compile_seconds"] = round(time.time() - t0, 1)
        return result
    except Exception as e:  # failures here are bugs in the system
        return dict(base, status="error", error=f"{type(e).__name__}: {e}",
                    traceback=traceback.format_exc()[-2000:],
                    compile_seconds=round(time.time() - t0, 1))


def iterate_cells(mesh_modes, archs=None, shapes=None):
    for arch in (archs or ARCH_IDS):
        for shape_name in (shapes or specs_mod.SHAPES):
            for multi_pod in mesh_modes:
                yield arch, shape_name, multi_pod


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(specs_mod.SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-sp", action="store_true",
                    help="disable sequence-parallel activation rules "
                         "(ablation; train cells need ~33 GB/device without)")
    ap.add_argument("--out", default=None, help="write JSONL here")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    mesh_modes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.mesh]
    archs = [args.arch] if args.arch else None
    shapes = [args.shape] if args.shape else None
    if not args.all and not args.arch:
        ap.error("pass --arch or --all")

    results = []
    for arch, shape_name, multi_pod in iterate_cells(mesh_modes, archs,
                                                     shapes):
        r = run_cell(arch, shape_name, multi_pod,
                     seq_parallel=not args.no_sp, save_hlo=args.save_hlo)
        results.append(r)
        line = json.dumps(r)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"# dryrun done: ok={n_ok} skipped={n_skip} errors={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
