"""Dry-run: every (arch x shape) cell must lower AND compile for the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh, on 512 virtual CPU devices.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b

``main`` asks for the 512 host devices before JAX creates its CPU backend,
so the flag holds only when this module runs as the program.  For a
program sized for one real chip, compile against a described TPU topology
instead (``tests/test_tpu_compile.py``).
"""
import argparse
import dataclasses
import glob
import json
import os
import shutil
import tempfile
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo as hlo_an
from repro.analysis import roofline as rf
from repro.configs import (ARCHS, SHAPES, cell_applicable, get_config,
                           input_specs)
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.sharding import rules_for, shardings_for, spec
from repro.training import steps as ST


def batch_axes(cfg, batch):
    ax = {}
    for k in batch:
        if k in ("tokens", "labels"):
            ax[k] = ("batch", "seq")
        else:
            ax[k] = ("batch", None, None)
    return ax


def build_cell(cfg, shape_name, mesh, overrides):
    """-> (fn, args, in_shardings, out_shardings, donate)"""
    cell = SHAPES[shape_name]
    mode = overrides.get("rules_mode") or \
        ("train" if cell.kind == "train" else "serve")
    rules = rules_for(mode, mesh.axis_names, fsdp=overrides.get("fsdp", True))
    ns = lambda s: NamedSharding(mesh, s)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    sp = lambda axes, shape: ns(spec(axes, rules, shape, mesh_shape))

    if cell.kind == "train":
        fn = ST.make_train_step(cfg, rules, remat=overrides.get("remat", "full"))
        state = ST.abstract_train_state(cfg)
        batch = input_specs(cfg, shape_name)
        st_sh = shardings_for(ST.train_state_axes(cfg), state, mesh, rules)
        b_sh = shardings_for(batch_axes(cfg, batch), batch, mesh, rules)
        metrics_sh = {k: ns(P()) for k in
                      ("loss", "ce", "aux", "grad_norm", "lr")}
        return (fn, (state, batch), (st_sh, b_sh), (st_sh, metrics_sh), (0,))

    params = M.abstract_params(cfg)
    p_axes = M.param_axes(cfg)
    if overrides.get("quant"):
        from repro.serving.quant import abstract_quantized, quantized_axes
        p_axes = quantized_axes(p_axes, params)
        params = abstract_quantized(params)
    p_sh = shardings_for(p_axes, params, mesh, rules)
    B = cell.batch
    if cell.kind == "prefill":
        fn = ST.make_prefill_step(cfg, rules, cache_len=cell.seq)
        batch = input_specs(cfg, shape_name)
        b_sh = shardings_for(batch_axes(cfg, batch), batch, mesh, rules)
        enc_S = cfg.encdec.encoder_seq if cfg.family == "audio" else 0
        cache_abs = jax.eval_shape(
            lambda: M.init_cache(cfg, B, cell.seq, enc_S=enc_S))
        cache_sh = shardings_for(M.cache_axes(cfg), cache_abs, mesh, rules)
        out_sh = ({"next_tokens": sp(("batch",), (B,)),
                   "last_logits": sp(("batch", "vocab"), (B, cfg.vocab_size))},
                  cache_sh)
        return (fn, (params, batch), (p_sh, b_sh), out_sh, ())

    # decode
    fn = ST.make_decode_step(cfg, rules)
    specs_ = input_specs(cfg, shape_name)
    cache_sh = shardings_for(M.cache_axes(cfg), specs_["caches"], mesh, rules)
    dp = sp(("batch",), (B,))
    in_sh = (p_sh, dp, dp, cache_sh)
    out_sh = (dp, sp(("batch", "vocab"), (B, cfg.vocab_size)), cache_sh)
    return (fn, (params, specs_["tokens"], specs_["pos"], specs_["caches"]),
            in_sh, out_sh, (3,))


def run_cell(arch, shape_name, multi_pod, overrides=None, keep_text=False):
    overrides = overrides or {}
    cfg = get_config(arch)
    for k, v in overrides.get("cfg", {}).items():
        cfg = dataclasses.replace(cfg, **{k: v})
    cell = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "num_chips": 512 if multi_pod else 256}
    skip = cell_applicable(cfg, shape_name)
    if skip:
        rec.update(status="skip", reason=skip)
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args, in_sh, out_sh, donate = build_cell(
            cfg, shape_name, mesh, overrides)
        t0 = time.time()
        dump_dir = tempfile.mkdtemp(prefix="hlo_spmd_")
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile(compiler_options={
                "xla_dump_to": dump_dir,
                "xla_dump_hlo_pass_re": "spmd-partitioning"})
            t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        text = compiled.as_text()
        # dtype-true (bf16) post-SPMD module for the roofline byte counts;
        # the final scheduled module inflates bf16 to f32 (CPU legalization)
        spmd_files = sorted(glob.glob(
            os.path.join(dump_dir, "*after_spmd-partitioning*.txt")),
            key=os.path.getsize)
        if spmd_files:
            spmd_text = open(spmd_files[-1]).read()
            cost = hlo_an.analyze(spmd_text, rec["num_chips"], mode="spmd")
        else:
            cost = hlo_an.analyze(text, rec["num_chips"])
        shutil.rmtree(dump_dir, ignore_errors=True)
        mf = rf.analytic_model_flops(cfg, cell.kind, cell.batch, cell.seq)
        roof = rf.from_hlo(cost, mf, rec["num_chips"])
        rec.update(
            status="ok", t_lower_s=round(t_lower, 2),
            t_compile_s=round(t_compile, 2),
            bytes_per_device=int(mem.argument_size_in_bytes +
                                 mem.temp_size_in_bytes +
                                 mem.output_size_in_bytes -
                                 mem.alias_size_in_bytes),
            # dtype-true resident state (params/caches/opt+outputs); the CPU
            # backend's temp is inflated by hoisted bf16->f32 legalization
            # copies that do not exist on TPU (see EXPERIMENTS.md §Dry-run)
            resident_bytes=int(mem.argument_size_in_bytes +
                               mem.output_size_in_bytes -
                               mem.alias_size_in_bytes),
            arg_bytes=int(mem.argument_size_in_bytes),
            temp_bytes=int(mem.temp_size_in_bytes),
            out_bytes=int(mem.output_size_in_bytes),
            alias_bytes=int(mem.alias_size_in_bytes),
            xla_flops_per_dev=float(ca.get("flops", 0.0)),
            hlo=cost, roofline=roof.as_dict(),
            model_flops_total=mf, hlo_text_len=len(text))
        if keep_text:
            rec["hlo_text"] = text
    except Exception as e:  # a failure here is a bug in the system
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
    return rec


def main():
    # before the first device query: the CPU backend reads this once
    jax.config.update("jax_num_cpu_devices", 512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--rules", default="", help="override rules mode, e.g. train_zero")
    ap.add_argument("--serve-quant", action="store_true",
                    help="int8 weight quantization for serve cells")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache for decode cells")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    overrides = {"remat": args.remat, "fsdp": not args.no_fsdp,
                 "rules_mode": args.rules or None,
                 "quant": args.serve_quant,
                 "cfg": {"kv_quant": True} if args.kv_quant else {}}
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, overrides)
                tag = f"-{args.tag}" if args.tag else ""
                name = f"{arch}_{shape}_{rec['mesh']}{tag}.json"
                with open(os.path.join(args.out, name), "w") as f:
                    json.dump(rec, f, indent=1)
                s = rec["status"]
                n_ok += s == "ok"
                n_skip += s == "skip"
                n_err += s == "error"
                if s == "ok":
                    r = rec["roofline"]
                    print(f"[{s:5s}] {arch:22s} {shape:12s} {rec['mesh']:8s} "
                          f"mem/dev={rec['bytes_per_device']/2**30:6.2f}GiB "
                          f"Tc={r['t_compute_s']:.3e} Tm={r['t_memory_s']:.3e} "
                          f"Tcoll={r['t_collective_s']:.3e} dom={r['dominant']:10s} "
                          f"compile={rec['t_compile_s']:.0f}s", flush=True)
                else:
                    print(f"[{s:5s}] {arch:22s} {shape:12s} {rec['mesh']:8s} "
                          f"{rec.get('reason', rec.get('error', ''))[:100]}",
                          flush=True)
    print(f"done: ok={n_ok} skip={n_skip} error={n_err}")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
