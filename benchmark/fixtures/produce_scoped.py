"""Records ``scoped_trace.xplane.pb``: a tiny round with every ``p2pfl.*`` scope.

    python -m benchmark.fixtures.produce_scoped [out.xplane.pb]     # on a TPU

Two nodes, one after the other; each runs two local steps of a two-layer scanned,
checkpointed block — ``LoRADense`` projections (base cast, base matmul,
adapter) around the flash kernels at (T, D) = (1024, 128) — under
``p2pfl.grad``, Adam under ``p2pfl.optimizer``, then a weighted mean and its
broadcast under ``p2pfl.fold``. The names are the program's own: the scopes
come from ``management/profiling.scope``, the kernels from
``ops/flash_attention``. One execution is traced with the options of
``run.Tracer``; ``benchmark.scope_selfcheck`` checks the result.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
T, D, HEADS, LAYERS, NODES, STEPS = 1024, 128, 2, 2, 2, 2


def build():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from p2pfl_tpu.management.profiling import scope
    from p2pfl_tpu.models.transformer import LoRADense
    from p2pfl_tpu.ops.flash_attention import flash_attention

    dim = HEADS * D
    interpret = jax.default_backend() != "tpu"

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x, _):
            q, k, v = (LoRADense(dim, rank=8, name=n)(x).reshape(1, T, HEADS, D) for n in ("wq", "wk", "wv"))
            out = flash_attention(q, k, v, True, None, interpret).reshape(1, T, dim)
            return x + LoRADense(dim, rank=8, name="wo")(out), None

    body = nn.remat(Layer, prevent_cse=False)
    model = nn.scan(body, variable_axes={"params": 0}, split_rngs={"params": True}, length=LAYERS)()
    tx = optax.adam(1e-3)

    def loss_fn(p, x):
        y, _ = model.apply({"params": p}, x, None)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    def node(p, o, xs):
        def step(carry, x):
            p_, o_ = carry
            with scope("grad"):
                loss, grads = jax.value_and_grad(loss_fn)(p_, x)
            with scope("optimizer"):
                updates, o_ = tx.update(grads, o_, p_)
                p_ = optax.apply_updates(p_, updates)
            return (p_, o_), loss

        (p, o), losses = jax.lax.scan(step, (p, o), xs)
        return p, o, jnp.mean(losses)

    @jax.jit
    def round_(params, opt, xs, weights):
        trained, out_opt, losses = jax.lax.map(lambda args: node(*args), (params, opt, xs))  # one node at a time
        with scope("fold"):
            wn = weights / jnp.sum(weights)
            mean = jax.tree.map(lambda a: jnp.tensordot(wn, a, axes=(0, 0)), trained)
            out = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (NODES, *a.shape)), mean)
        return out, out_opt, jnp.mean(losses)

    key = jax.random.PRNGKey(0)
    x0 = jnp.zeros((1, T, dim), jnp.bfloat16)
    one = jax.jit(lambda k: model.init(k, x0, None)["params"])(key)
    params = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (NODES, *a.shape)), one)
    opt = jax.vmap(tx.init)(params)
    xs = jax.random.normal(key, (NODES, STEPS, 1, T, dim), jnp.bfloat16)
    return round_, (params, opt, xs, jnp.asarray([1.0, 3.0]))


def record(out: Path) -> None:
    import jax

    round_, (params, opt, xs, weights) = build()
    params, opt, loss = round_(params, opt, xs, weights)  # compile + warm
    float(loss)
    directory = Path(tempfile.mkdtemp(prefix="scoped_trace_"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(directory), profiler_options=options)
    with jax.profiler.TraceAnnotation("p2pfl:round"):
        params, opt, loss = round_(params, opt, xs, weights)
        float(loss)
    jax.profiler.stop_trace()
    files = sorted(directory.glob("plugins/profile/*/*.xplane.pb"))
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(files[-1], out)
    shutil.rmtree(directory, ignore_errors=True)
    print(f"wrote {out} ({out.stat().st_size} bytes) on {jax.devices()[0].device_kind}, loss {float(loss):.6f}")


if __name__ == "__main__":
    record(Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "scoped_trace.xplane.pb")
