"""GCN as the configurations run it: each layer sums its in-neighbours'
rows, divides by the in-degree (at least 1), adds the vertex's own row and
applies one weight matrix and bias; ReLU between layers.  This is the mean
aggregator with a self term; Kipf and Welling (arXiv:1609.02907) normalise
by ``D^-1/2 (A+I) D^-1/2`` instead (listed under ``assumed``)."""
import jax
import jax.numpy as jnp


def init_params(key, dims):
    """Weights ~ N(0, 1/fan_in), biases zero; one layer per pair of dims."""
    layers = []
    for l, (di, do) in enumerate(zip(dims[:-1], dims[1:])):
        k = jax.random.fold_in(key, l)
        layers.append(dict(
            w=jax.random.normal(k, (di, do), jnp.float32) / jnp.sqrt(di),
            b=jnp.zeros((do,), jnp.float32)))
    return {"layers": layers}


def layer(p, H, ctx, last: bool):
    nbr = ctx.gather_sum(ctx.table(H)) / ctx.deg
    z = ctx.mm(nbr + H, p["w"]) + p["b"]
    return z if last else jax.nn.relu(z)
