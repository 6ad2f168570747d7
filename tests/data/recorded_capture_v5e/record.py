"""How the capture beside this file was recorded (`chiprun -- python3 record.py <dir>` from the repo root): a toy
program with two named scopes (forward and backward) driven under two timer spans, as
the Dreamer-V3 loop drives its train program. Run as `python3`, as the benchmark is."""
import glob, os, shutil, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp, numpy as np
from sheeprl_tpu.utils.timer import timer

out = sys.argv[1]

@jax.jit
def train_step(w, x):
    def loss(w):
        with jax.named_scope("encoder"):
            h = jnp.tanh(x @ w["a"])
        with jax.named_scope("rssm"):
            def body(c, _):
                return jnp.tanh(c @ w["b"]), None
            h, _ = jax.lax.scan(body, h, None, length=4)
        return jnp.mean(h ** 2)
    g = jax.grad(loss)(w)
    with jax.named_scope("optimizer"):
        return jax.tree_util.tree_map(lambda p, q: p - 0.1 * q, w, g)

w = {"a": jnp.ones((256, 512)) * 1e-2, "b": jnp.ones((512, 512)) * 1e-3}
x = jnp.ones((128, 256))
w = train_step(w, x); jax.block_until_ready(w)
timer.disabled = False
shutil.rmtree(out, ignore_errors=True)
jax.profiler.start_trace(out)
for i in range(2):
    timer.iteration = i
    with timer("Time/env_interaction_time"):
        with timer("act"):
            time.sleep(0.002)
    with timer("Time/train_time"):
        w = train_step(w, x)
        with timer("act_view"):
            with timer("act_view.fetch"):
                host = np.asarray(w["b"])
jax.profiler.stop_trace()
for f in glob.glob(out + "/**/*", recursive=True):
    if os.path.isfile(f):
        print(f, os.path.getsize(f))
