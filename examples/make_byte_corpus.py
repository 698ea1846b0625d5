"""Build a byte-level token corpus from text that ships inside the image.

The reference trains on an auto-downloaded dataset
(/root/reference/main.py:43-51); this environment has zero egress, so the
convergence runs use real local text instead:
the Python standard library's sources, the installed numpy/jax package
sources, and this repository's docs. That is real, structured,
natural-ish data — exactly what a byte-level LM can learn from — and it
is reproducible from a fresh image with this one script.

The train/val split hashes each file's CONTENT, so byte-identical files
(vendored copies, repeated licenses) always land in the same split — the
"no validation text appears in training" guarantee holds even across
duplicated files.

Output: ``<out>_train.bin`` / ``<out>_val.bin`` — flat little-endian
uint16 token files in the nanoGPT convention that
``tpudist.data.lm.load_token_stream`` reads (byte ids 0..255; uint16 so
the same file drives models with any vocab_size >= 256, e.g. GPT-2's
50257). The split is by whole file (a deterministic hash), not by byte
offset, so no validation window overlaps training text.

Usage::

    python examples/make_byte_corpus.py --out pytext --max_mb 24
"""

from __future__ import annotations

import argparse
import hashlib
import sysconfig
from pathlib import Path

import numpy as np


def source_roots() -> list[Path]:
    roots = [Path(sysconfig.get_paths()["stdlib"])]
    for pkg in ("numpy", "jax", "flax", "optax"):
        try:
            mod = __import__(pkg)
            roots.append(Path(mod.__file__).parent)
        except Exception:
            pass
    repo = Path(__file__).resolve().parent.parent
    roots += [repo / "docs", repo / "tpudist"]
    return roots


def gather_files(roots: list[Path]) -> list[Path]:
    files: list[Path] = []
    for root in roots:
        if not root.exists():
            continue
        for pattern in ("*.py", "*.md", "*.rst", "*.txt"):
            for p in root.rglob(pattern):
                # filter on the path BELOW the root: the roots themselves
                # live under site-packages, which must not exclude them
                rel = p.relative_to(root)
                if p.name.startswith("test_"):
                    continue
                if {"test", "tests", "site-packages"} & set(rel.parts[:-1]):
                    continue
                files.append(p)
    # deterministic order independent of filesystem enumeration
    return sorted(set(files))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="pytext", help="output file prefix")
    ap.add_argument("--max_mb", type=float, default=24.0,
                    help="stop collecting after this many MB of text")
    ap.add_argument("--val_frac", type=int, default=16,
                    help="1/N of files (by hash) go to validation")
    args = ap.parse_args()
    if args.val_frac < 2:
        ap.error(f"--val_frac must be >= 2 (got {args.val_frac}): 1/N of "
                 "files go to validation, so N=1 would put EVERY file in "
                 "val and N<=0 is undefined")

    budget = int(args.max_mb * 1e6)
    train_parts: list[bytes] = []
    val_parts: list[bytes] = []
    total = 0
    for path in gather_files(source_roots()):
        try:
            data = path.read_bytes()
        except OSError:
            continue
        if not data or len(data) > 2_000_000 or b"\x00" in data:
            continue  # NUL-free text only, so NUL can serve as the doc separator
        try:
            data.decode("utf-8")
        except UnicodeDecodeError:
            continue
        h = int.from_bytes(hashlib.sha1(data).digest()[:4], "big")
        (val_parts if h % args.val_frac == 0 else train_parts).append(data)
        total += len(data)
        if total >= budget:
            break

    # validate BOTH splits before writing EITHER file: a tiny --max_mb
    # budget can fill one split before the hash ever routes a file to the
    # other, and writing the good split first would leave a fresh train
    # .bin silently pairing with a stale val .bin from an earlier run
    empty = [n for n, p in (("train", train_parts), ("val", val_parts)) if not p]
    if empty:
        raise SystemExit(
            f"make_byte_corpus: the {'/'.join(empty)} split is EMPTY "
            f"(budget {args.max_mb} MB consumed before any file hashed "
            "into it) — raise --max_mb or adjust --val_frac; nothing "
            "was written"
        )
    for name, parts in (("train", train_parts), ("val", val_parts)):
        blob = b"\x00".join(parts)  # NUL = doc separator (NUL-bearing files were filtered)
        tokens = np.frombuffer(blob, np.uint8).astype(np.uint16)
        out = f"{args.out}_{name}.bin"
        tokens.tofile(out)
        print(f"{out}: {tokens.size:,} tokens from {len(parts)} files")


if __name__ == "__main__":
    main()
