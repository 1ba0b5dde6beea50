"""Plain float32 reference of GAT (Velickovic et al., ICLR 2018) as
published for inductive PPI (section 3.3), biases left out.

Layer l has heads k = 1..K_l.  Head k transforms ``z_k = H W_lk`` and
attends with ``alpha_k``, the softmax over ``N(i) + {i}`` of
``LeakyReLU_0.2(a_src_lk . z_i + a_dst_lk . z_j)``, exact (no threshold).
A hidden layer concatenates its heads' ``alpha_k z_k``, adds the skip
projection ``H P_l`` where it has one, and applies ELU; the output layer
averages its heads.  The configuration's ``model`` block gives the heads,
head widths, combination and skip layers; the forward reads them from the
parameters' names and shapes.  Every product goes through ``dot`` so the
same code gives the reference (float32 at ``Precision.HIGHEST``) and its
lower-precision control.
"""
import numpy as np

SLOPE = 0.2


def _layers(config):
    """Per layer: (heads, head width, averaged, has a skip projection)."""
    m = config["model"]
    n = int(m["layers"])
    heads, widths, combine = m["heads"], m["head_widths"], m["combine"]
    if not len(heads) == len(widths) == len(combine) == n:
        raise ValueError("gat: one head count, head width and combination "
                         "per layer")
    if list(combine) != ["concat"] * (n - 1) + ["mean"]:
        raise ValueError(f"gat combines heads as published: concatenated "
                         f"in hidden layers, averaged in the output layer, "
                         f"not {combine}")
    if int(widths[-1]) != int(m["classes"]):
        raise ValueError("gat: the output heads' width is the class count")
    skip = {int(s) for s in m.get("skip", ())}
    return [(int(h), int(w), l == n, l in skip)
            for l, (h, w) in enumerate(zip(heads, widths), start=1)]


def dims(config):
    """Layer widths, input first: a hidden layer is heads x head width."""
    d = [int(config["features"]["width"])]
    for heads, width, mean, _ in _layers(config):
        d.append(width if mean else heads * width)
    return d


def param_shapes(config):
    """``{name: shape}`` of every parameter, named as the program names
    them, in a fixed order."""
    d, out = dims(config), {}
    for l, (heads, width, _, skip) in enumerate(_layers(config), start=1):
        for k in range(1, heads + 1):
            out[f"Wg{l}h{k}"] = (d[l - 1], width)
            out[f"a_src{l}h{k}"] = (width, 1)
            out[f"a_dst{l}h{k}"] = (width, 1)
        if skip:
            out[f"P{l}"] = (d[l - 1], d[l])
    return out


def init_weights(config, seed):
    """Glorot-uniform float32 parameters from ``seed``, made on the device
    in one jitted call."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, shape) in zip(keys, shapes.items()):
            lim = np.sqrt(6.0 / (shape[0] + shape[1]))
            out[name] = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
        return out

    return make(jax.random.PRNGKey(seed % (1 << 32)))


def program_spec(config):
    """The program's ``GNNModelSpec`` for the configuration."""
    from repro.core.compiler import GNNModelSpec
    from repro.core.ir import Activation

    m = config["model"]
    return GNNModelSpec(
        "gat", dims(config), activation=Activation(m["activation"]),
        gat_heads=tuple(h for h, _, _, _ in _layers(config)),
        gat_skip=tuple(int(s) for s in m.get("skip", ())),
        att_slope=float(m["att_slope"]),
        att_threshold=float(m["att_threshold"]))


def attention_bytes(config, n, nnz):
    """HBM bytes the attention of one inference needs, counted on the
    support: per head the weights of its ``nnz`` support entries, its
    (n, head width) features and two scores per vertex, in float32."""
    return float(sum(heads * (4 * nnz + 4 * n * width + 8 * n)
                     for heads, width, _, _ in _layers(config)))


def _attention(support, z, a_src, a_dst, dot, pairs):
    import jax.numpy as jnp

    pairs += [(z, a_src), (z, a_dst)]
    e = dot(z, a_src) + dot(z, a_dst).T
    e = jnp.where(e >= 0, e, SLOPE * e)
    e = jnp.where(support, e, -jnp.inf)
    ex = jnp.where(support, jnp.exp(e - jnp.max(e, axis=1, keepdims=True)),
                   0.0)
    return ex / jnp.sum(ex, axis=1, keepdims=True)


def forward(params, adj, h0, dot, n_layers):
    """Logits and the (X, Y) operands of every product, in order.  Every
    vertex has a self loop, so no softmax row is empty."""
    import jax
    import jax.numpy as jnp

    support = adj["gcn"] != 0
    pairs = []
    h = h0
    for layer in range(1, n_layers + 1):
        heads = []
        k = 1
        while f"Wg{layer}h{k}" in params:
            w = params[f"Wg{layer}h{k}"]
            pairs.append((h, w))
            z = dot(h, w)
            alpha = _attention(support, z, params[f"a_src{layer}h{k}"],
                               params[f"a_dst{layer}h{k}"], dot, pairs)
            pairs.append((alpha, z))
            heads.append(dot(alpha, z))
            k += 1
        if layer == n_layers:
            h = sum(heads[1:], heads[0]) / len(heads)
            continue
        out = jnp.concatenate(heads, axis=1)
        if f"P{layer}" in params:
            pairs.append((h, params[f"P{layer}"]))
            out = out + dot(h, params[f"P{layer}"])
        h = jax.nn.elu(out)
    return h, pairs
