"""Rehearse a cell's memory on a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/size_cells.py <workload> [max_batch]

Compiles, for one chip of a described v5e, the cell's decode step and
its largest prefill step (the largest group at the largest bucket the
traffic reaches; for interleaved prefill, one slice of that group) at
the cell's max_batch and max_len, and prints each program's
``memory_analysis()`` and the peak it implies beside the resident
weights and KV pool. Nothing runs; this sizes ``max_batch``.
"""

from __future__ import annotations

import os
import sys

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), p) for p in ("src", "")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(argv):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.models import get_model
    from repro.serving.scheduler import bucket_len, make_buckets, pad_group
    from bench import harness, loadgen

    jax.config.update("jax_enable_compilation_cache", False)
    spec = harness.load_cell(argv[0])
    c = dict(spec["cell"]["engine"])
    if len(argv) > 1:
        c["max_batch"] = int(argv[1])
    api = get_model(harness.program_config(spec["config"]))
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.default_backend = lambda: "tpu"     # resolve kernels as on the chip

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    b, L = c["max_batch"], c["max_len"]
    params = shapes(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    pool = shapes(jax.eval_shape(lambda: api.init_cache(b, L)))
    gib = 2.0 ** -30
    nbytes = lambda t: sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(t))
    resident = nbytes(params) + nbytes(pool)
    print(f"{argv[0]} max_batch {b} max_len {L}: params "
          f"{nbytes(params) * gib:.3f} GiB, pool {nbytes(pool) * gib:.3f} GiB")

    def report(name, compiled, extra):
        ma = compiled.memory_analysis()
        peak = resident + extra + ma.temp_size_in_bytes + max(
            0, ma.output_size_in_bytes - ma.alias_size_in_bytes)
        print(f"  {name}: args {ma.argument_size_in_bytes * gib:.3f} out "
              f"{ma.output_size_in_bytes * gib:.3f} alias "
              f"{ma.alias_size_in_bytes * gib:.3f} temp "
              f"{ma.temp_size_in_bytes * gib:.3f} GiB -> peak about "
              f"{peak * gib:.3f} GiB", flush=True)

    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=dev)
    report(f"decode B={b}", jax.jit(api.decode, donate_argnums=1).lower(
        params, pool, tok).compile(), 0)
    hi = loadgen.length_range(spec["traffic"])[1]
    blen = bucket_len(hi, make_buckets(L))
    gp = pad_group(b)
    if c.get("interleave"):
        chunk = min(c.get("prefill_chunk") or 8, blen)
        st = shapes(jax.eval_shape(lambda: api.prefill_slice_init(gp, L)))
        args = (params, st[0], jax.ShapeDtypeStruct((gp, chunk), jnp.int32,
                                                    sharding=dev),
                st[1], jax.ShapeDtypeStruct((gp,), jnp.int32, sharding=dev),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=dev))
        report(f"prefill slice {gp}x{chunk}",
               jax.jit(api.prefill_slice, donate_argnums=(1, 3)).lower(
                   *args).compile(), nbytes(st))
    else:
        fn = jax.jit(lambda p, t, sl: api.prefill(
            p, {"tokens": t}, max_len=L, seq_lens=sl))
        report(f"prefill {gp}x{blen}", fn.lower(
            params, jax.ShapeDtypeStruct((gp, blen), jnp.int32, sharding=dev),
            jax.ShapeDtypeStruct((gp,), jnp.int32, sharding=dev)).compile(), 0)


if __name__ == "__main__":
    main(sys.argv[1:])
