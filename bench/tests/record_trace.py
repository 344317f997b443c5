"""Record the small trace that ``test_trace.py`` reduces (run on a host
with four TPU chips):

    python bench/tests/record_trace.py <out_dir>

A shard_map over four chips runs the program's own compressed all-gather
and reduce-scatter under the ``taco`` codec around a matrix product, and a
native all-reduce, three times inside ``bench/data`` spans, as the
benchmark's data source marks its steps.  It writes the profiler trace
and the compiled module's HLO text (for the kernel names).
"""
import glob
import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(out: str):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh, shard_map
    from repro.core import collectives as cc
    from repro.core.registry import from_spec

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < 4:
        raise SystemExit("needs four TPU chips")
    plan = from_spec("taco")
    mesh = make_mesh((4,), ("model",))

    def body(x, w):
        full = cc.all_gather_c(x, "model", 1, plan.tp_fwd, plan.tp_bwd)
        y = full @ w
        out = cc.psum_scatter_c(y, "model", 1, plan.tp_fwd, plan.tp_bwd)
        return out + jax.lax.psum(jnp.sum(out), "model").astype(out.dtype)

    fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=(P(None, "model"), P()),
                           out_specs=P(None, "model"), check_vma=False))
    x = jax.device_put(jnp.ones((1, 2048, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P(None, "model")))
    w = jax.device_put(jnp.eye(1024, dtype=jnp.bfloat16) * 0.5,
                       NamedSharding(mesh, P()))
    fn(x, w).block_until_ready()
    hlo = fn.lower(x, w).compile().as_text()

    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    jax.profiler.start_trace(os.path.join(out, "profile"))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/data"):
            pass
        with jax.profiler.TraceAnnotation("bench/trainer"):
            fn(x, w).block_until_ready()
    with jax.profiler.TraceAnnotation("bench/data"):
        pass
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(out, "profile", "**", "*.xplane.pb"),
                    recursive=True)[0]
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(os.path.join(out, "profile"))
    with gzip.open(os.path.join(out, "small.hlo.txt.gz"), "wt") as f:
        f.write(hlo)


if __name__ == "__main__":
    main(sys.argv[1])
