"""GraphSAGE (mean aggregator) as the configurations run it: each layer
multiplies the vertex's own row by ``w_self`` and the mean of its
in-neighbours' rows (sum over the in-degree, at least 1) by ``w_nbr``, and
adds a bias; ReLU between layers (Hamilton et al., arXiv:1706.02216, with
the two products summed, as OGB's example does, not concatenated)."""
import jax
import jax.numpy as jnp


def init_params(key, dims):
    """Weights ~ N(0, 1/fan_in), biases zero; one layer per pair of dims."""
    layers = []
    for l, (di, do) in enumerate(zip(dims[:-1], dims[1:])):
        ks, kn = jax.random.split(jax.random.fold_in(key, l))
        layers.append(dict(
            w_self=jax.random.normal(ks, (di, do), jnp.float32) / jnp.sqrt(di),
            w_nbr=jax.random.normal(kn, (di, do), jnp.float32) / jnp.sqrt(di),
            b=jnp.zeros((do,), jnp.float32)))
    return {"layers": layers}


def layer(p, H, ctx, last: bool):
    nbr = ctx.gather_sum(ctx.table(H)) / ctx.deg
    z = ctx.mm(H, p["w_self"]) + ctx.mm(nbr, p["w_nbr"]) + p["b"]
    return z if last else jax.nn.relu(z)
