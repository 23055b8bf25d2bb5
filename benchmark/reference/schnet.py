"""SchNet continuous-filter convolution (Schuett et al. 2018, as PyG
``CFConv`` inside HydraGNN's ``SCFStack``).

W_ij = Dense(ssp(Dense(rbf(d_ij)))) * C(d_ij), with rbf the Gaussian
smearing of the distance over [0, cutoff], ssp the shifted softplus and C
the cosine cutoff; out_i = W2 ( sum_j (W1 x_j) * W_ij ) + b2.
Flax names in the program's tree: Dense_0, Dense_1 the filter network,
Dense_2 = W1 (no bias), Dense_3 = W2.
"""

import jax
import jax.numpy as jnp

from reference.common import dense


def conv(p, x, b, mcfg, quant=None):
    n = x.shape[0]
    r, g = mcfg["radius"], int(mcfg["num_gaussians"])
    diff = b.pos[b.recv] - b.pos[b.send]
    d = jnp.sqrt((diff * diff).sum(-1))
    offset = jnp.linspace(0.0, r, g)
    coeff = -0.5 / (r / (g - 1)) ** 2
    rbf = jnp.exp(coeff * (d[:, None] - offset[None, :]) ** 2)
    filt = dense(p["Dense_0"], rbf, quant)
    filt = jax.nn.softplus(filt) - jnp.log(2.0)
    filt = dense(p["Dense_1"], filt, quant)
    cut = jnp.where(d <= r, 0.5 * (jnp.cos(d * jnp.pi / r) + 1.0), 0.0)
    filt = filt * (cut * b.edge_w)[:, None]
    h = dense(p["Dense_2"], x, quant, bias=False)
    agg = jax.ops.segment_sum(h[b.send] * filt, b.recv, n, indices_are_sorted=True)
    return dense(p["Dense_3"], agg, quant)
