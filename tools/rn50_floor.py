"""Framework-free ResNet-50 train-step floor probe.

Hand-rolled raw-JAX RN50 (no paddle_tpu imports on the model path):
bf16 params/activations, NHWC, fused-form BN (single-pass fp32 stats,
folded scale/shift), SGD+momentum, one donated jit. If THIS gets the
same ~2260 img/s as the framework bench, the wall is the XLA conv path
on this chip, not framework overhead; if it's faster, the delta is our
overhead budget, and its HLO is the template to chase.

Usage: python tools/rn50_floor.py [batch]   (prints one JSON line)
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BLOCKS = {50: ((3, 64), (4, 128), (6, 256), (3, 512))}


def _conv(x, w, stride=1):
    import jax.lax as lax
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn_train(x, gamma, beta):
    """Single-pass batch-norm: fp32 sibling reductions, bf16 apply."""
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    mean_sq = jnp.mean(jnp.square(xf), axis=(0, 1, 2))
    var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
    inv = gamma * (var + 1e-5) ** -0.5
    return (x * inv.astype(x.dtype)
            + (beta - mean * inv).astype(x.dtype))


def init_params(rng):
    import numpy as np
    p = {}

    def conv(name, kh, kw, cin, cout):
        fan = kh * kw * cin
        p[name] = (rng.normal(0, (2.0 / fan) ** 0.5,
                              (kh, kw, cin, cout)).astype("float32"))

    def bn(name, c):
        p[name + "/g"] = np.ones(c, "float32")
        p[name + "/b"] = np.zeros(c, "float32")

    conv("stem", 7, 7, 3, 64)
    bn("stem_bn", 64)
    cin = 64
    for si, (nblocks, width) in enumerate(BLOCKS[50]):
        cout = width * 4
        for bi in range(nblocks):
            pre = f"s{si}b{bi}"
            if bi == 0:
                conv(pre + "/proj", 1, 1, cin, cout)
                bn(pre + "/proj_bn", cout)
            conv(pre + "/c1", 1, 1, cin, width)
            bn(pre + "/bn1", width)
            conv(pre + "/c2", 3, 3, width, width)
            bn(pre + "/bn2", width)
            conv(pre + "/c3", 1, 1, width, cout)
            bn(pre + "/bn3", cout)
            cin = cout
    p["fc/w"] = rng.normal(0, 0.01, (2048, 1000)).astype("float32")
    p["fc/b"] = np.zeros(1000, "float32")
    return p


def forward(params, x):
    import jax
    import jax.numpy as jnp
    import jax.lax as lax
    bf = jnp.bfloat16
    pb = {k: v.astype(bf) if v.ndim == 4 or k == "fc/w" else v
          for k, v in params.items()}
    h = _conv(x, pb["stem"], 2)
    h = jax.nn.relu(_bn_train(h, params["stem_bn/g"],
                              params["stem_bn/b"]))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), "SAME")
    cin = 64
    for si, (nblocks, width) in enumerate(BLOCKS[50]):
        cout = width * 4
        for bi in range(nblocks):
            pre = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            if bi == 0:
                sc = _bn_train(_conv(h, pb[pre + "/proj"], stride),
                               params[pre + "/proj_bn/g"],
                               params[pre + "/proj_bn/b"])
            else:
                sc = h
            y = jax.nn.relu(_bn_train(_conv(h, pb[pre + "/c1"], 1),
                                      params[pre + "/bn1/g"],
                                      params[pre + "/bn1/b"]))
            y = jax.nn.relu(_bn_train(_conv(y, pb[pre + "/c2"], stride),
                                      params[pre + "/bn2/g"],
                                      params[pre + "/bn2/b"]))
            y = _bn_train(_conv(y, pb[pre + "/c3"], 1),
                          params[pre + "/bn3/g"],
                          params[pre + "/bn3/b"])
            h = jax.nn.relu(y + sc)
            cin = cout
    h = jnp.mean(h, axis=(1, 2))
    return h.astype(bf) @ pb["fc/w"] + params["fc/b"]


def main() -> None:
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.sysconfig import enable_compile_cache
    enable_compile_cache()

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    rng = np.random.default_rng(0)
    params = init_params(rng)
    vel = {k: np.zeros_like(v) for k, v in params.items()}
    x = jnp.asarray(rng.normal(0, 1, (batch, 224, 224, 3)),
                    jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)))

    def loss_fn(p, xb, yb):
        logits = forward(p, xb).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, yb[:, None], 1).mean()

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, v, xb, yb):
        loss, g = jax.value_and_grad(loss_fn)(p, xb, yb)
        v = jax.tree.map(lambda vi, gi: 0.9 * vi + gi, v, g)
        p = jax.tree.map(lambda pi, vi: pi - 0.1 * vi, p, v)
        return p, v, loss

    for i in range(4):  # donated-layout fixpoint
        t0 = time.time()
        params, vel, loss = step(params, vel, x, labels)
        print(f"[floor] warmup {i}: {time.time() - t0:.2f}s "
              f"loss={float(loss):.3f}", file=sys.stderr)
    n = 20
    t0 = time.perf_counter()
    for _ in range(n):
        params, vel, loss = step(params, vel, x, labels)
    _ = float(loss)  # host read ends the timed region
    dt = (time.perf_counter() - t0) / n
    ips = batch / dt
    print(json.dumps({
        "metric": "raw-JAX ResNet-50 floor images/sec/chip",
        "value": round(ips, 1), "unit": "images/sec",
        "ms_per_step": round(dt * 1e3, 2), "batch": batch,
        "vs_baseline": round(ips * 12.3e9 / 1e12 / (0.8 * 197.0), 4),
        "device": str(jax.devices()[0].device_kind)}))


if __name__ == "__main__":
    main()
