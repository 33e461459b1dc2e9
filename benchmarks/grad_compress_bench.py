"""Gradient-compression collective bytes: fp32/bf16 psum vs int8
compressed_psum, measured by the HLO analyzer on an 8-device subprocess
(wire bytes per device; the ratio is mesh-size independent)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.training.grad_compress import compressed_psum
from repro.launch.mesh import make_mesh
from repro.analysis.hlo import analyze

mesh = make_mesh((8,), ("data",))
x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)

def plain(v):
    return jax.shard_map(lambda t: jax.lax.psum(t, "data"), mesh=mesh,
                         in_specs=P(None, None), out_specs=P(None, None),
                         check_vma=False)(v)

def comp(v):
    return compressed_psum(v, mesh, "data")

out = {}
with jax.set_mesh(mesh):
    for name, fn in (("psum_fp32", plain), ("psum_int8_ef", comp)):
        c = jax.jit(fn).lower(x).compile()
        a = analyze(c.as_text(), 8)
        out[name] = {"coll_bytes_per_dev": a["coll_bytes"],
                     "coll": a["coll"]}
print("JSON:" + json.dumps(out))
"""


def main(quick: bool = False):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    # the child counts HLO bytes on virtual CPU devices: it must never ask
    # for an accelerator, which the parent process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", CODE, src],
                         capture_output=True, text=True, timeout=560, env=env)
    line = [l for l in out.stdout.splitlines() if l.startswith("JSON:")]
    if out.returncode != 0 or not line:
        raise RuntimeError(f"grad_compress child failed (exit "
                           f"{out.returncode}): {out.stderr[-2000:]}")
    d = json.loads(line[0][5:])
    fp32 = d["psum_fp32"]["coll_bytes_per_dev"]
    int8 = d["psum_int8_ef"]["coll_bytes_per_dev"]
    return [{"name": "allreduce_fp32", "us_per_call": 0,
             "derived": f"wire_bytes/dev={fp32:.0f}"},
            {"name": "allreduce_int8_ef", "us_per_call": 0,
             "derived": f"wire_bytes/dev={int8:.0f} "
                        f"(reduction {fp32/max(int8,1):.1f}x)"}]


if __name__ == "__main__":
    for r in main():
        print(r)
