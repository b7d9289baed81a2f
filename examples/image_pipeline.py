"""Image-processing pipeline (paper §6.4, Listing 17): a stream of images
flows Emit → StencilEngine(greyscale) → StencilEngine(edge-detect 3×3 or
5×5) → Collect, with the convolution backed by the Pallas stencil kernel.

    PYTHONPATH=src python examples/image_pipeline.py [--kernel 5]
"""

import argparse

import jax.numpy as jnp
import numpy as np

from repro.core import (Collect, Emit, Network, StencilEngine, build,
                        run_sequential, verify)
from repro.launch.cache import enable_compile_cache

EDGE3 = jnp.asarray([[-1, -1, -1], [-1, 8, -1], [-1, -1, -1]], jnp.float32)
EDGE5 = jnp.asarray([[-1] * 5, [-1] * 5, [-1, -1, 24, -1, -1],
                     [-1] * 5, [-1] * 5], jnp.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", type=int, choices=(3, 5), default=5)
    ap.add_argument("--size", type=int, default=96)
    ap.add_argument("--images", type=int, default=3)
    ap.add_argument("--pallas", action="store_true", default=True)
    args = ap.parse_args()
    enable_compile_cache()

    rng = np.random.default_rng(0)
    # synthetic "photos": smooth gradients + a bright square to edge-detect
    imgs = []
    for i in range(args.images):
        img = np.linspace(0, 1, args.size)[:, None] * np.ones(args.size)
        s = args.size // 4
        img[s * (i % 2 + 1):s * (i % 2 + 2), s:2 * s] += 2.0
        imgs.append(jnp.asarray(
            np.stack([img, img * 0.5, img * 0.25], -1), jnp.float32))

    def grey(img):  # the user's greyScaleMethod
        return img @ jnp.asarray([0.299, 0.587, 0.114], jnp.float32)

    kern = EDGE5 if args.kernel == 5 else EDGE3
    net = Network("image")
    net.add(
        Emit(lambda i: imgs[i], name="emit"),
        StencilEngine(functionMethod=grey, name="engine1"),
        StencilEngine(convolutionData=kern, use_pallas=args.pallas,
                      name="engine2"),
        Collect(lambda acc, x: acc + [np.asarray(x)], init=[],
                name="collector"),
    )
    verify(net)
    seq = run_sequential(net, args.images)["collector"]
    cn = build(net)
    par = cn.run(instances=args.images)["collector"]
    same = all(np.allclose(a, b, atol=1e-3) for a, b in zip(seq, par))
    print(f"sequential == parallel ({args.images} images, {args.kernel}x"
          f"{args.kernel} kernel, pallas={args.pallas}): {same}")
    # streaming microbatch execution: images flow through the engine chain
    strm = cn.run_streaming(instances=args.images,
                            microbatch_size=2)["collector"]
    same_s = all(np.array_equal(a, b) for a, b in zip(seq, strm))
    print(f"sequential == streaming: {same_s}  [{cn.stream_stats.summary()}]")
    # edges found where the bright square sits?
    edges = np.abs(par[0]) > 1.0
    print(f"edge pixels detected: {int(edges.sum())} "
          f"({'OK' if edges.sum() > 0 else 'FAIL'})")


if __name__ == "__main__":
    main()
