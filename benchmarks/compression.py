"""Cross-pod gradient compression — collective-bytes measurement.

On the 2-pod mesh the gradient all-reduce spans the inter-pod link (DCI,
~10x slower than intra-pod ICI).  `repro.distributed.compress` quantizes
the cross-pod contribution to int8 with error feedback.  This benchmark
lowers the explicit shard_map reduction both ways on the production
2x16x16 mesh and reports the collective bytes from the scan-aware HLO
analysis — the structural 4x payload reduction on the pod axis.

Run standalone (needs its own process for the 512-device env):
    PYTHONPATH=src python -m benchmarks.compression
"""

import os


def main(out=None):
    out = out if out is not None else []
    if os.environ.get("XLA_FLAGS", "") != \
            "--xla_force_host_platform_device_count=512":
        # re-exec in a clean process with the device-count flag set
        import subprocess
        import sys
        # the child compiles for fake host devices: it never needs (and
        # must not contend with its parent for) an accelerator
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=512"}
        r = subprocess.run([sys.executable, "-m", "benchmarks.compression"],
                           capture_output=True, text=True, env=env,
                           timeout=1200)
        for line in r.stdout.splitlines():
            if line.startswith("compress."):
                out.append(line)
                print(line)
        if r.returncode != 0:
            raise RuntimeError(f"compression child failed: "
                               f"{r.stderr[-300:]}")
        return

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.compress import tree_compress_psum, \
        init_error_feedback
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=True)
    gshape = (4096, 2048)  # a stand-in gradient shard (per pod-replica)

    def reduce_plain(g):
        return jax.lax.psum(g, "pod") / 2

    def reduce_int8(g, err):
        red, new_err = tree_compress_psum({"g": g}, {"g": err}, "pod")
        return red["g"], new_err

    spec = NamedSharding(mesh, P("pod", None))
    g = jax.ShapeDtypeStruct(gshape, jnp.float32)

    plain = jax.jit(
        jax.shard_map(reduce_plain, mesh=mesh, in_specs=P("pod"),
                      out_specs=P("pod"), check_vma=False),
    ).lower(g).compile()
    comp = jax.jit(
        jax.shard_map(reduce_int8, mesh=mesh,
                      in_specs=(P("pod"), P("pod")),
                      out_specs=(P("pod"), P("pod")), check_vma=False),
    ).lower(g, g).compile()

    b_plain = analyze_hlo(plain.as_text()).collective_bytes
    b_comp = analyze_hlo(comp.as_text()).collective_bytes
    out.append(f"compress.plain_f32,{b_plain:.0f},collective_bytes")
    out.append(f"compress.int8_ef,{b_comp:.0f},collective_bytes|"
               f"reduction={b_plain / max(b_comp, 1):.2f}x")
    for line in out[-2:]:
        print(line)


if __name__ == "__main__":
    main()
