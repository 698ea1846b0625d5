"""sklearn's bundled handwritten-digits set as a tpudist dataset.

The reference trains on auto-downloaded CIFAR-100
(/root/reference/main.py:43-51). In a zero-egress environment that download
is impossible, so real-data convergence runs (`--dataset digits`) use
the one REAL image dataset shipped inside the image: scikit-learn's
``load_digits`` — 1,797 real 8×8 grayscale handwritten digits (a UCI/NIST
subset), 10 classes. Images are nearest-neighbor upscaled to 32×32 RGB
uint8 so the CIFAR model geometry (``small_inputs`` ResNets, 4-pixel-patch
ViT) and the ``to_tensor`` transform apply unchanged.

The train/val split is a deterministic seeded permutation so every process
computes the identical split with no coordination — the same
shared-seed-instead-of-broadcast idiom as ``create_train_state``.
"""

from __future__ import annotations

import numpy as np

_SPLIT_SEED = 0
_TRAIN_FRACTION = 0.8


def load_digits_dataset(
    train: bool = True, *, upscale: int = 4, rgb: bool = True
) -> dict[str, np.ndarray]:
    """The digits images as ``{"image": uint8 NHWC, "label": int32}``.

    ``upscale`` repeats each pixel into an ``upscale×upscale`` block
    (8×8 → 32×32 at the default); ``rgb`` replicates the gray channel to 3
    channels. Pixel intensities (0..16 in the source) are rescaled to the
    full 0..255 range the CIFAR transforms expect.
    """
    from sklearn.datasets import load_digits

    bunch = load_digits()
    images = bunch.images  # [1797, 8, 8] float64, values 0..16
    labels = bunch.target.astype(np.int32)

    rng = np.random.Generator(np.random.PCG64(_SPLIT_SEED))
    order = rng.permutation(len(labels))
    n_train = int(len(labels) * _TRAIN_FRACTION)
    keep = order[:n_train] if train else order[n_train:]

    img = np.clip(images[keep] * (255.0 / 16.0), 0, 255).astype(np.uint8)
    if upscale > 1:
        img = img.repeat(upscale, axis=1).repeat(upscale, axis=2)
    img = img[..., None]
    if rgb:
        img = np.repeat(img, 3, axis=-1)
    return {
        "image": np.ascontiguousarray(img),
        "label": np.ascontiguousarray(labels[keep]),
    }
