"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell
and extract memory/cost/roofline artifacts.

``main()`` asks XLA for 512 host devices before anything touches a
device (jax locks the device count when its backend first starts).
Importing this module changes nothing.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out experiments/dryrun]

Each cell writes experiments/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, collective breakdown and roofline terms.
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, input_specs, cell_status
from repro.launch import hlo_analysis as HA
from repro.models import model as M
from repro.models import layers as L
from repro.models.config import ArchConfig
from repro.train.loop import TrainConfig, make_train_step, make_optimizer
from repro.train.optimizer import AdamState, AdafactorState, FactoredMoment
from repro.dist.sharding import factored_moment_specs, resolve_spec


# ----------------------------------------------------------- shardings

def batch_shardings(cfg, mesh, specs):
    def tok(sd):
        ndim = len(sd.shape)
        return NamedSharding(mesh, resolve_spec(
            sd.shape, ("batch",) + (None,) * (ndim - 1), mesh))
    return jax.tree.map(tok, specs)


def opt_state_shardings(opt_name, cfg, mesh):
    """Optimizer-state shardings mirroring the param PartitionSpecs.

    Adafactor's factored moments are re-resolved from the *abstract*
    params' (shape, logical) through dist.sharding.factored_moment_specs
    — not sliced out of the param specs, which under-shards (see its
    docstring; unit-tested in tests/test_dist_sharding.py)."""
    ab = M.abstract_params(cfg)
    ns = lambda spec: NamedSharding(mesh, spec)
    rep = NamedSharding(mesh, P())
    if opt_name == "adamw":
        t = jax.tree.map(ns, L.pspec_tree(ab, mesh))
        return AdamState(mu=t, nu=t, count=rep)

    def fact(a):
        if len(a.shape) >= 2:
            row, col = factored_moment_specs(a.shape, a.logical, mesh)
            return FactoredMoment(row=ns(row), col=ns(col))
        return ns(resolve_spec(a.shape, a.logical, mesh))
    moments = jax.tree.map(fact, ab, is_leaf=L.is_pab)
    return AdafactorState(moments=moments, count=rep)


def opt_state_shapes(opt, cfg):
    return jax.eval_shape(opt.init, M.param_shapes(cfg))


def cache_shardings(cfg, mesh, batch, max_len, dtype=jnp.bfloat16):
    logical = M.cache_logical(cfg)
    abstract = M.cache_abstract(cfg, batch, max_len, dtype)
    is_ls = lambda v: isinstance(v, tuple) and all(
        isinstance(e, (str, type(None))) for e in v)
    flat_ab, treedef = jax.tree.flatten(abstract)
    flat_ls = treedef.flatten_up_to(logical)
    assert all(is_ls(v) for v in flat_ls)
    out = [NamedSharding(mesh, resolve_spec(ab.shape, ls, mesh))
           for ab, ls in zip(flat_ab, flat_ls)]
    return jax.tree.unflatten(treedef, out)


def pick_optimizer_name(cfg: ArchConfig) -> str:
    # fp32 Adam state for >=30B params cannot fit a 256-chip v5e pod;
    # use factored second moments (see DESIGN.md §5)
    return "adamw" if cfg.n_params() < 30e9 else "adafactor"


# ------------------------------------------------------------ lowering

def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_status(cfg, shape)
    if skip:
        return {"status": skip}

    # sharding profile: pure DP for small-model train/prefill; decode
    # always keeps the serving profile (sequence-sharded KV caches —
    # pure DP would replicate a 32k-deep cache per device)
    from repro.dist.sharding import (set_active_rules, rules_for,
                                     DEFAULT_RULES)
    set_active_rules(DEFAULT_RULES if shape.kind == "decode"
                     else rules_for(cfg.n_params()))

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    specs = input_specs(cfg, shape)
    param_sh = M.param_shardings(cfg, mesh)
    p_shapes = M.param_shapes(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)

    if shape.kind == "train":
        tc = TrainConfig(optimizer=pick_optimizer_name(cfg), microbatch=1)
        opt = make_optimizer(tc)
        step = make_train_step(cfg, tc, mesh=mesh, opt=opt)
        o_shapes = opt_state_shapes(opt, cfg)
        opt_sh = opt_state_shardings(tc.optimizer, cfg, mesh)
        b_sh = batch_shardings(cfg, mesh, specs["batch"])
        rep = NamedSharding(mesh, P())
        metrics_sh = {k: rep for k in ("loss", "nll", "aux", "grad_norm", "lr")}
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, b_sh),
                out_shardings=(param_sh, opt_sh, metrics_sh),
                donate_argnums=(0, 1),
            ).lower(p_shapes, o_shapes, specs["batch"])
        model_flops = 6.0 * cfg.n_active_params() * tokens
    elif shape.kind == "prefill":
        order = ["tokens"] + [k for k in ("enc_frames", "extra_embeds")
                              if k in specs]

        # vlm: the patch-embedding prefix occupies cache positions too
        max_len = shape.seq_len + (cfg.vis_seq if cfg.family == "vlm" else 0)

        def serve_prefill(params, *inputs):
            kw = dict(zip(order, inputs))
            return M.prefill(cfg, params, kw.pop("tokens"),
                             max_len=max_len, mesh=mesh, **kw)
        b_sh = batch_shardings(cfg, mesh, specs)
        with mesh:
            lowered = jax.jit(
                serve_prefill,
                in_shardings=(param_sh,) + tuple(b_sh[k] for k in order),
            ).lower(p_shapes, *[specs[k] for k in order])
        model_flops = 2.0 * cfg.n_active_params() * tokens
    else:  # decode
        def serve_step(params, cache, tokens, positions):
            return M.decode_step(cfg, params, cache, tokens, positions,
                                 mesh=mesh)
        cache_sh = cache_shardings(cfg, mesh, shape.global_batch,
                                   shape.seq_len)
        tok_sh = NamedSharding(mesh, resolve_spec(
            (shape.global_batch, 1), ("batch", None), mesh))
        logits_sh = NamedSharding(mesh, resolve_spec(
            (shape.global_batch, 1, cfg.vocab),
            ("batch", None, "vocab"), mesh))
        with mesh:
            lowered = jax.jit(
                serve_step,
                in_shardings=(param_sh, cache_sh, tok_sh, tok_sh),
                out_shardings=(logits_sh, cache_sh),
                donate_argnums=(1,),
            ).lower(p_shapes, specs["cache"], specs["tokens"],
                    specs["positions"])
        model_flops = 2.0 * cfg.n_active_params() * tokens

    return {"status": "ok", "lowered": lowered, "n_chips": n_chips,
            "model_flops": model_flops, "cfg": cfg}


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             save_hlo: bool = False):
    mesh_name = "multi" if multi_pod else "single"
    tag = f"{arch}__{shape_name}__{mesh_name}"
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    t0 = time.time()
    try:
        cell = lower_cell(arch, shape_name, multi_pod)
        if cell["status"] != "ok":
            result["status"] = cell["status"]
            print(f"[dryrun] {tag}: {cell['status']}")
        else:
            lowered = cell["lowered"]
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            mem = compiled.memory_analysis()
            print(f"[dryrun] {tag} memory_analysis: {mem}")
            ca = compiled.cost_analysis()
            print(f"[dryrun] {tag} cost_analysis keys: "
                  f"{sorted(list(ca))[:8] if ca else None}")
            cfg = cell["cfg"]
            cap = max(jnp.dtype(cfg.params_dtype).itemsize,
                      jnp.dtype(cfg.compute_dtype).itemsize)
            roof, coll = HA.roofline_from_compiled(
                compiled, cell["n_chips"], cell["model_flops"],
                native_cap_bytes=cap)
            mem_fields = {}
            for f in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                v = getattr(mem, f, None)
                if v is not None:
                    mem_fields[f] = int(v)
            result.update({
                "status": "ok",
                "lower_s": t1 - t0, "compile_s": t2 - t1,
                "memory_analysis": mem_fields,
                "bytes_per_device": int(
                    mem_fields.get("argument_size_in_bytes", 0)
                    + mem_fields.get("temp_size_in_bytes", 0)),
                "roofline": roof.as_dict(),
                "collectives": {"by_kind": coll.by_kind,
                                "op_counts": coll.op_counts},
            })
            if save_hlo:
                (out_dir / f"{tag}.hlo.txt").write_text(compiled.as_text())
            print(f"[dryrun] {tag}: OK lower={t1-t0:.1f}s "
                  f"compile={t2-t1:.1f}s bottleneck="
                  f"{result['roofline']['bottleneck']}")
    except Exception as e:
        result["status"] = f"FAIL: {type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()
        print(f"[dryrun] {tag}: FAIL {e}")
    result["total_s"] = time.time() - t0
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{tag}.json", "w") as f:
        json.dump(result, f, indent=1, default=str)
    return result


HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"


def main():
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + HOST_DEVICES_FLAG).strip()
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    out = Path(args.out)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                for mp in meshes:
                    cells.append((a, s, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, mp) for mp in meshes]

    n_fail = 0
    for a, s, mp in cells:
        tag = f"{a}__{s}__{'multi' if mp else 'single'}"
        if args.skip_existing and (out / f"{tag}.json").exists():
            prev = json.loads((out / f"{tag}.json").read_text())
            if str(prev.get("status", "")).startswith(("ok", "skip")):
                print(f"[dryrun] {tag}: cached ({prev['status'][:40]})")
                continue
        r = run_cell(a, s, mp, out, save_hlo=args.save_hlo)
        if str(r["status"]).startswith("FAIL"):
            n_fail += 1
    print(f"[dryrun] done, failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
