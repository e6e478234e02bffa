"""Record the small TPU trace ``bench/tests/data/small.xplane.pb`` that
``test_trace_reduce.py`` reads: three steps, each a host span
``sched.next_step`` that sleeps 20 ms (the device idles) and a span
``engine.run_packed`` that runs a matmul and the mixed paged attention
kernel, inside one ``bench.traced`` span. Run on the chip:

    python3 bench/tests/record_trace.py <out.xplane.pb>
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import mixed_paged_attention

    assert jax.devices()[0].platform == "tpu"
    KV, G, d, page, P, nb = 2, 6, 128, 16, 64, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((8, KV, G, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((P, KV, page, d)), jnp.bfloat16)
    cu = jnp.asarray([0, 1, 2, 3, 8], jnp.int32)
    kl = jnp.asarray([100, 20, 64, 40], jnp.int32)
    bt = jnp.asarray(rng.integers(0, P, (4, nb)), jnp.int32)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    attn = lambda: mixed_paged_attention(q, k, k, cu, kl, bt, qb=8)
    jax.block_until_ready((mm(x), attn()))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("sched.next_step"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("engine.run_packed"):
                jax.block_until_ready((mm(x), attn()))
    jax.profiler.stop_trace()
    shutil.copy(next(Path(tmp).rglob("*.xplane.pb")), out)
    print(f"wrote {out} ({Path(out).stat().st_size} bytes)")


if __name__ == "__main__":
    main(sys.argv[1])
