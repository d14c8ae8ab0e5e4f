"""Sharded on-disk image-folder input tier.

The reference example trains from an ImageFolder directory through
``torchvision.transforms`` + a multi-worker ``DataLoader``
(examples/imagenet/main_amp.py:229-246). This module is that tier for
the TPU stack: a ``root/<class>/*.ppm|*.npy`` scan
(:class:`ImageFolder`), per-epoch deterministic sharded shuffling keyed
by ``(seed, epoch, process_index)`` (:class:`ShardedImageFolderLoader`),
and batch assembly on a host worker pool — file bytes are read in python
threads (I/O releases the GIL) and decoded + cropped + flipped in ONE
threaded native pass (``csrc/image_pipeline.cpp``
``apex_tpu_decode_ppm_augment_u8``), so the python step loop only ever
sees finished uint8 NHWC batches. Compose with
:class:`~apex_tpu.data.DevicePrefetcher` for transfer overlap;
normalization stays on device (``normalize_imagenet`` fused into the
consumer).

Sharding contract (multi-host data parallelism):

- the epoch order is ONE global permutation keyed by ``(seed, epoch)``;
- process ``i`` of ``n`` takes rows ``perm[i::n]`` — shards are disjoint
  by construction and their union covers the epoch;
- augmentation draws come from ``(seed, epoch, process_index)`` so no
  two shards (or epochs) reuse crops/flips, yet every run of the same
  shard is bit-identical.

Formats: binary PPM (P6) rides the native decode tier; ``.npy`` (uint8
HWC arrays) decodes host-side via numpy — the escape hatch for tests
and toolchain-less installs. :func:`write_image_folder` generates a
synthetic dataset directory (tests).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional, Sequence

import numpy as np

__all__ = ["ImageFolder", "ShardedImageFolderLoader", "encode_ppm",
           "write_image_folder"]

_EXTENSIONS = (".ppm", ".npy")


def encode_ppm(img: np.ndarray) -> bytes:
    """Encode a uint8 HWC (c=3) array as a binary P6 blob."""
    img = np.ascontiguousarray(img, np.uint8)
    if img.ndim != 3 or img.shape[2] != 3:
        raise ValueError(f"want [h, w, 3] uint8, got {img.shape}")
    h, w, _ = img.shape
    return b"P6\n%d %d\n255\n" % (w, h) + img.tobytes()


def write_image_folder(root: str, *, classes: int = 4,
                       per_class: int = 16,
                       size: "tuple[int, int]" = (40, 40),
                       seed: int = 0, fmt: str = "ppm") -> "list[str]":
    """Generate a synthetic ``root/class_k/img_j.<fmt>`` dataset (the
    on-disk mini-dataset of the e2e tests and the ``--data synth``
    bench arm). Deterministic in ``seed``. Returns the class dirs."""
    if fmt not in ("ppm", "npy"):
        raise ValueError(f"fmt must be ppm|npy, got {fmt!r}")
    rs = np.random.RandomState(seed)
    h, w = size
    dirs = []
    for k in range(classes):
        d = os.path.join(root, f"class_{k:03d}")
        os.makedirs(d, exist_ok=True)
        dirs.append(d)
        for j in range(per_class):
            img = rs.randint(0, 256, (h, w, 3), dtype=np.uint8)
            p = os.path.join(d, f"img_{j:05d}.{fmt}")
            if fmt == "ppm":
                with open(p, "wb") as f:
                    f.write(encode_ppm(img))
            else:
                np.save(p, img)
    return dirs


class ImageFolder:
    """``root/<class>/*`` scan: sorted class dirs -> integer labels,
    sorted files within each class — the deterministic sample list every
    process shares (the permutation, not the scan, is the shuffle)."""

    def __init__(self, root: str,
                 extensions: Sequence[str] = _EXTENSIONS):
        root = os.path.abspath(root)
        if not os.path.isdir(root):
            raise FileNotFoundError(f"dataset root {root} is not a dir")
        self.root = root
        self.classes = sorted(
            d for d in os.listdir(root)
            if os.path.isdir(os.path.join(root, d)))
        if not self.classes:
            raise ValueError(f"no class subdirectories under {root}")
        samples: list[tuple[str, int]] = []
        for label, cls in enumerate(self.classes):
            d = os.path.join(root, cls)
            for name in sorted(os.listdir(d)):
                if os.path.splitext(name)[1].lower() in extensions:
                    samples.append((os.path.join(d, name), label))
        if not samples:
            raise ValueError(f"no {'/'.join(extensions)} files under "
                             f"{root}")
        self.samples = samples

    def __len__(self) -> int:
        return len(self.samples)


def _load_npy_crop(path: str, off_u: "tuple[float, float]", flip: bool,
                   crop: "tuple[int, int]") -> np.ndarray:
    img = np.load(path)
    if img.ndim != 3 or img.dtype != np.uint8:
        raise ValueError(f"{path}: want uint8 HWC, got "
                         f"{img.dtype} {img.shape}")
    h, w, _ = img.shape
    ch, cw = crop
    if ch > h or cw > w:
        raise ValueError(f"{path}: crop {crop} larger than image "
                         f"({h}x{w})")
    t = int(off_u[0] * (h - ch + 1))
    l = int(off_u[1] * (w - cw + 1))
    out = img[t:t + ch, l:l + cw]
    return out[:, ::-1, :] if flip else out


class ShardedImageFolderLoader:
    """Iterate an :class:`ImageFolder` as augmented uint8 NHWC batches,
    assembled ahead of consumption on a host worker pool.

    ::

        ds = ImageFolder("/data/imagenet/train")
        loader = ShardedImageFolderLoader(ds, batch_size=256,
                                          crop=(224, 224), seed=0,
                                          process_index=jax.process_index(),
                                          process_count=jax.process_count())
        for x_u8, labels in DevicePrefetcher(loader, depth=2):
            ...

    ``train=True``: random crop + horizontal flip, fresh shard-local
    randomness per epoch. ``train=False``: center crop, no flip, no
    shuffle (still sharded). Re-iterating advances the epoch (call
    :meth:`set_epoch` to pin it, e.g. on resume).
    """

    def __init__(self, dataset: "ImageFolder | str", batch_size: int,
                 crop: "tuple[int, int]", *, train: bool = True,
                 flip: Optional[bool] = None, seed: int = 0,
                 process_index: int = 0, process_count: int = 1,
                 workers: int = 2, lookahead: Optional[int] = None,
                 drop_remainder: bool = True, nthreads: int = 0):
        if isinstance(dataset, str):
            dataset = ImageFolder(dataset)
        self.dataset = dataset
        if not (0 <= process_index < process_count):
            raise ValueError(f"process_index {process_index} out of "
                             f"range for process_count {process_count}")
        n_shard = len(range(process_index, len(dataset), process_count))
        if batch_size < 1 or (drop_remainder and batch_size > n_shard):
            raise ValueError(f"bad batch_size {batch_size} for shard of "
                             f"{n_shard} samples")
        self._batch = int(batch_size)
        self._crop = (int(crop[0]), int(crop[1]))
        self._train = bool(train)
        self._flip = self._train if flip is None else bool(flip)
        self._seed = int(seed)
        self._pi, self._pc = int(process_index), int(process_count)
        self._workers = max(1, int(workers))
        # at-least-2-deep: one batch decoding while one is consumed
        self._lookahead = (max(2, self._workers) if lookahead is None
                           else max(1, int(lookahead)))
        self._drop = drop_remainder
        self._nthreads = nthreads
        self._epoch = 0
        self._n_shard = n_shard

    def set_epoch(self, epoch: int) -> "ShardedImageFolderLoader":
        self._epoch = int(epoch)
        return self

    @property
    def epoch(self) -> int:
        return self._epoch

    def __len__(self) -> int:
        if self._drop:
            return self._n_shard // self._batch
        return -(-self._n_shard // self._batch)

    def shard_indices(self, epoch: int) -> np.ndarray:
        """This process's rows of the epoch's GLOBAL permutation —
        ``perm(seed, epoch)[process_index::process_count]``. Disjoint
        across processes, union = the whole epoch; the determinism and
        disjointness contract the tests pin."""
        n = len(self.dataset)
        if self._train:
            order = np.random.RandomState(
                (self._seed, epoch)).permutation(n)
        else:
            order = np.arange(n)
        return order[self._pi::self._pc].astype(np.int64)

    # -- batch assembly (runs on the worker pool) -------------------------
    def _assemble(self, rows: np.ndarray, uni: np.ndarray,
                  flips: np.ndarray) -> "tuple[np.ndarray, np.ndarray]":
        from apex_tpu.utils import native
        ch, cw = self._crop
        samples = self.dataset.samples
        labels = np.asarray([samples[r][1] for r in rows], np.int32)
        out = np.empty((rows.size, ch, cw, 3), np.uint8)
        ppm_pos, blobs = [], []
        for b, r in enumerate(rows):
            path = samples[r][0]
            if path.lower().endswith(".ppm"):
                with open(path, "rb") as f:   # I/O: GIL released
                    blobs.append(f.read())
                ppm_pos.append(b)
            else:
                out[b] = _load_npy_crop(path, uni[b], bool(flips[b]),
                                        self._crop)
        if ppm_pos:
            offs = np.empty((len(ppm_pos), 2), np.int32)
            for i, b in enumerate(ppm_pos):
                h, w = native.ppm_dims(blobs[i])
                if ch > h or cw > w:
                    raise ValueError(
                        f"{samples[rows[b]][0]}: crop {self._crop} "
                        f"larger than image ({h}x{w})")
                offs[i, 0] = int(uni[b, 0] * (h - ch + 1))
                offs[i, 1] = int(uni[b, 1] * (w - cw + 1))
            # decode + crop + flip in one threaded native pass
            dec = native.decode_ppm_augment_u8(
                blobs, offs, flips[ppm_pos], self._crop,
                nthreads=self._nthreads)
            out[ppm_pos] = dec
        return out, labels

    def __iter__(self) -> Iterator["tuple[np.ndarray, np.ndarray]"]:
        epoch = self._epoch
        self._epoch += 1
        rows = self.shard_indices(epoch)
        stop = len(self) * self._batch if self._drop else rows.size
        # ALL augmentation randomness drawn up front on the iterating
        # thread, keyed by (seed, epoch, process_index): worker timing
        # can never reorder draws, so batches are bit-deterministic
        rs = np.random.RandomState((self._seed, epoch, self._pi))
        if self._train:
            uni = rs.random_sample((rows.size, 2))
        else:
            # center crop: floor(u * (n - c + 1)) == (n - c) // 2 for
            # every (n, c) when u sits just under one half
            uni = np.full((rows.size, 2), 0.5 - 1e-7)
        if self._flip:
            flips = (rs.random_sample(rows.size) < 0.5).astype(np.uint8)
        else:
            flips = np.zeros(rows.size, np.uint8)
        spans = [(lo, min(lo + self._batch, stop))
                 for lo in range(0, stop, self._batch)]

        def submit(pool, lo, hi):
            return pool.submit(self._assemble, rows[lo:hi], uni[lo:hi],
                               flips[lo:hi])

        with ThreadPoolExecutor(max_workers=self._workers) as pool:
            pending = []
            it = iter(spans)
            for lo, hi in it:
                pending.append(submit(pool, lo, hi))
                if len(pending) >= self._lookahead:
                    break
            for lo, hi in it:
                yield pending.pop(0).result()
                pending.append(submit(pool, lo, hi))
            while pending:
                yield pending.pop(0).result()
