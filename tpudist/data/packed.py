"""Pre-decoded packed image datasets — the streaming-ImageNet throughput fix.

The reference's input pipeline decodes JPEGs on the host every epoch
(/root/reference/main.py:54-63 drives torchvision's loader; an ImageFolder
re-decodes every sample every pass). PIL JPEG decode tops out at O(100)
images/sec per host core, far under what a chip consumes at BASELINE
configs 2/3 — on a small-host TPU attach the streaming path is decode-bound
no matter how deep the prefetch queue in front of it (the benchmark has no
vision cell: not measured on the chip). The TPU-native fix is the MLPerf
one: **decode once, train from pixels**.

:func:`pack_image_folder` runs the one-time pass: scan the class tree
(torchvision ``ImageFolder`` semantics, same scan as
``tpudist.data.imagenet``), decode every image through the deterministic
eval transform (resize-short-side + center crop — bit-identical to
``ImageFolderLoader(train=False)`` pixels), and write a fixed-shape uint8
memmap:

- ``<prefix>_images.npy`` — ``[N, size, size, 3]`` uint8, written through a
  memmap so the pack never holds the dataset in RAM;
- ``<prefix>_labels.npy`` — ``[N]`` int32;
- ``<prefix>_meta.json`` — class names + image size + provenance.

:func:`load_packed` memory-maps the pack back as the ordinary
``{"image", "label"}`` array dataset, so the WHOLE existing array pipeline
applies unchanged: ``DataLoader`` (C++ fused gather) streams batches at
memcpy speed (~GB/s, 30×+ the decode rate), and ``DeviceCachedLoader``
stages the pack to HBM once and ships only indices per step — the two
framework answers to a decode-bound and a link-bound attach respectively.

Trade-off, stated plainly: packed pixels are the EVAL transform, so the
per-epoch RandomResizedCrop augmentation of the streaming loader does not
apply — use :class:`tpudist.data.imagenet.ImageFolderLoader` when the
recipe needs fresh crops and the host has the cores to decode them; pack
when input throughput is the binding constraint (the SURVEY.md §7 hard-part
#1 regime).

CLI::

    python -m tpudist.data.packed --root /data/imagenet/train --out inpack \
        --image_size 224
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from tpudist.data.imagenet import _resize_center_crop, scan_image_folder


def pack_image_folder(
    root: str | os.PathLike,
    out_prefix: str | os.PathLike,
    *,
    image_size: int = 224,
    workers: int | None = None,
    classes: list[str] | None = None,
) -> dict:
    """One-time decode pass: image-folder tree → packed uint8 memmap.

    Returns a summary dict (``n``, ``seconds``, ``images_per_sec``,
    ``bytes``) — the pack rate IS the host's sustained JPEG decode rate,
    to set against the rate at which the chip consumes images.
    Pass the train split's ``classes`` when packing a val split (same
    label-stability contract as ``scan_image_folder``).
    """
    paths, labels, classes = scan_image_folder(root, classes)
    n = len(paths)
    out_prefix = str(out_prefix)
    workers = (
        max(1, workers) if workers is not None
        else min(os.cpu_count() or 8, 16)
    )

    from PIL import Image

    def decode(i: int) -> None:
        with Image.open(paths[i]) as img:
            img = _resize_center_crop(img.convert("RGB"), image_size)
            images[i] = np.asarray(img, np.uint8)

    t0 = time.perf_counter()
    # write-through memmap: the pack never materializes the dataset in RAM
    images = np.lib.format.open_memmap(
        out_prefix + "_images.npy", mode="w+", dtype=np.uint8,
        shape=(n, image_size, image_size, 3),
    )
    with ThreadPoolExecutor(max_workers=workers) as pool:
        # consume the iterator to surface decode errors
        for _ in pool.map(decode, range(n)):
            pass
    images.flush()
    dt = time.perf_counter() - t0
    np.save(out_prefix + "_labels.npy", np.asarray(labels, np.int32))
    meta = {
        "classes": classes,
        "image_size": image_size,
        "n": n,
        "source_root": str(Path(root).resolve()),
        "transform": "resize_short_side_256/224 + center_crop (eval)",
    }
    with open(out_prefix + "_meta.json", "w") as f:
        json.dump(meta, f)
    return {
        "n": n,
        "seconds": dt,
        "images_per_sec": n / dt if dt > 0 else float("inf"),
        "bytes": int(images.nbytes),
    }


def load_packed(prefix: str | os.PathLike, *, mmap: bool = True) -> dict:
    """Packed dataset → ``{"image": [N,s,s,3] uint8, "label": [N] int32,
    "classes": [...]}``.

    ``mmap=True`` (default) memory-maps the pixels: batch gathers fault in
    only the pages they touch, so a pack larger than RAM still streams.
    The returned dict drops straight into ``DataLoader`` /
    ``DeviceCachedLoader`` / ``evaluate``.
    """
    prefix = str(prefix)
    with open(prefix + "_meta.json") as f:
        meta = json.load(f)
    images = np.load(
        prefix + "_images.npy", mmap_mode="r" if mmap else None
    )
    labels = np.load(prefix + "_labels.npy")
    if images.shape[0] != meta["n"] or images.shape[0] != labels.shape[0]:
        raise ValueError(
            f"pack {prefix} is inconsistent: images {images.shape[0]} rows, "
            f"labels {labels.shape[0]}, meta n={meta['n']} — repack"
        )
    return {"image": images, "label": labels, "classes": meta["classes"]}


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", required=True,
                    help="image-folder tree (root/<class>/*.jpg)")
    ap.add_argument("--out", required=True, help="output file prefix")
    ap.add_argument("--image_size", type=int, default=224)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--classes_from", default=None,
                    help="train-split pack prefix whose class list keys the "
                    "labels (pass when packing a val split)")
    args = ap.parse_args(argv)
    classes = None
    if args.classes_from:
        with open(args.classes_from + "_meta.json") as f:
            classes = json.load(f)["classes"]
    out = pack_image_folder(
        args.root, args.out, image_size=args.image_size,
        workers=args.workers, classes=classes,
    )
    print(
        f"packed {out['n']} images ({out['bytes'] / 1e6:.0f} MB) in "
        f"{out['seconds']:.1f}s = {out['images_per_sec']:.0f} images/sec "
        f"sustained decode"
    )


if __name__ == "__main__":
    main()
