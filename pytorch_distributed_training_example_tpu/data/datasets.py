"""Datasets for the five reference configs (BASELINE.json).

A dataset is anything with ``__len__`` and ``__getitem__(i) -> dict[str,
np.ndarray]`` (batches are dicts; the train step consumes ``image``/``label``
or ``tokens``). Real data:

- CIFAR-10 from the standard ``cifar-10-batches-py`` pickle layout.
- ImageNet-style class-per-directory trees via :class:`FolderDataset`
  (JPEG decode through PIL/libjpeg-turbo, or the native C++ engine's libjpeg
  path — data/native_loader.py); the synthetic variants below stand in when
  no dataset is on disk (``chip_smoke.py`` trains on them; the benchmark's
  cells put their own seeded rows in the loader, ``chipbench/rows/``).
"""

from __future__ import annotations

import os
import pickle
from typing import Sequence

import numpy as np

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)
CIFAR_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR_STD = np.array([0.2470, 0.2435, 0.2616], np.float32)


class SyntheticImageDataset:
    """Deterministic fake images+labels; shaped/normalized like the real thing.

    Each image is noise plus a fixed per-class pattern, so classes are
    separable — few-epoch convergence tests measure real learning rather
    than memorization of pure noise.
    """

    def __init__(self, num_examples: int = 51200, image_size: int = 224,
                 num_classes: int = 1000, seed: int = 0,
                 noise_seed: int | None = None, augment: bool = False):
        self.num_examples = num_examples
        self.image_size = image_size
        self.num_classes = num_classes
        self.seed = seed
        # Per-sample noise stream. Class PATTERNS are keyed on `seed` so
        # train and eval share the learnable signal, but a split built with
        # a different `noise_seed` draws DISJOINT samples — a genuinely
        # held-out set (the r4 artifact's eval indices reused the train
        # noise stream, so "held-out" partially scored seen images).
        self.noise_seed = seed if noise_seed is None else noise_seed
        self.augment = augment
        self.epoch = 0
        pat_rng = np.random.default_rng(seed + 12345)
        # Low-res patterns upsampled at access: O(classes * 8*8*3) memory.
        self._pat_res = min(8, image_size)
        self._patterns = pat_rng.standard_normal(
            (min(num_classes, 1024), self._pat_res, self._pat_res, 3)
        ).astype(np.float32)

    def __len__(self):
        return self.num_examples

    def __getitem__(self, i: int):
        rng = np.random.default_rng((self.noise_seed, i))
        label = np.int32(i % self.num_classes)
        img = rng.standard_normal(
            (self.image_size, self.image_size, 3), np.float32)
        pat = self._patterns[label % len(self._patterns)]
        rep = self.image_size // self._pat_res
        if rep > 1:
            pat = np.repeat(np.repeat(pat, rep, 0), rep, 1)
        img = 0.7 * img[: pat.shape[0], : pat.shape[1]] + 0.7 * pat
        if img.shape[0] != self.image_size:  # image_size not divisible by 8
            full = rng.standard_normal(
                (self.image_size, self.image_size, 3)).astype(np.float32)
            full[: img.shape[0], : img.shape[1]] = img
            img = full
        img = img.astype(np.float32)
        if self.augment:
            # CIFAR-style train transform (reflect-pad-4 crop + flip),
            # reseeded per epoch like CIFAR10/FolderDataset.
            arng = np.random.default_rng((self.noise_seed, self.epoch, i))
            padded = np.pad(img, ((4, 4), (4, 4), (0, 0)), mode="reflect")
            y, x = arng.integers(0, 9, size=2)
            img = padded[y: y + self.image_size, x: x + self.image_size]
            if arng.integers(0, 2):
                img = img[:, ::-1]
            img = np.ascontiguousarray(img)
        return {"image": img, "label": label}


class CIFAR10:
    """CIFAR-10 from the canonical python pickle batches (NHWC float32, normalized).

    The reference's CPU-runnable dev config (BASELINE.json configs[0]).
    Train-time augmentation: random crop with 4px pad + horizontal flip.
    """

    mean = CIFAR_MEAN
    std = CIFAR_STD

    def __init__(self, root: str, train: bool = True, augment: bool | None = None,
                 seed: int = 0):
        base = os.path.join(root, "cifar-10-batches-py")
        files = [f"data_batch_{i}" for i in range(1, 6)] if train else ["test_batch"]
        images, labels = [], []
        for f in files:
            with open(os.path.join(base, f), "rb") as fh:
                d = pickle.load(fh, encoding="bytes")
            images.append(d[b"data"])
            labels.extend(d[b"labels"])
        data = np.concatenate(images).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        # Kept uint8: 4x less host RAM, and the native C++ engine reads it
        # directly; normalization happens at access time (affine ops commute
        # with crop/flip, so results match normalizing first).
        self.images_u8 = np.ascontiguousarray(data)
        self.labels = np.asarray(labels, np.int32)
        self.augment = train if augment is None else augment
        self.seed = seed
        self.epoch = 0

    def __len__(self):
        return len(self.labels)

    def __getitem__(self, i: int):
        img = self.images_u8[i]
        if self.augment:
            rng = np.random.default_rng((self.seed, self.epoch, i))
            padded = np.pad(img, ((4, 4), (4, 4), (0, 0)), mode="reflect")
            y, x = rng.integers(0, 9, size=2)
            img = padded[y : y + 32, x : x + 32]
            if rng.random() < 0.5:
                img = img[:, ::-1]
        out = img.astype(np.float32) / 255.0
        out = (out - CIFAR_MEAN) / CIFAR_STD
        return {"image": out, "label": self.labels[i]}


def random_resized_crop_params(rng, width: int, height: int,
                               scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)):
    """Sample an (x, y, w, h) crop box — torchvision RandomResizedCrop semantics.

    10 rejection-sampling tries over (area-scale, log-aspect), then the
    ratio-clamped center-crop fallback. Coordinates are in original pixels.
    """
    area = width * height
    log_ratio = (np.log(ratio[0]), np.log(ratio[1]))
    for _ in range(10):
        target_area = area * rng.uniform(*scale)
        aspect = np.exp(rng.uniform(*log_ratio))
        w = int(round(np.sqrt(target_area * aspect)))
        h = int(round(np.sqrt(target_area / aspect)))
        if 0 < w <= width and 0 < h <= height:
            x = int(rng.integers(0, width - w + 1))
            y = int(rng.integers(0, height - h + 1))
            return x, y, w, h
    in_ratio = width / height
    if in_ratio < ratio[0]:
        w = width
        h = int(round(w / ratio[0]))
    elif in_ratio > ratio[1]:
        h = height
        w = int(round(h * ratio[1]))
    else:
        w, h = width, height
    return (width - w) // 2, (height - h) // 2, w, h


def center_crop_box(width: int, height: int, image_size: int,
                    resize_short: int | None = None):
    """Eval crop box in ORIGINAL pixel coords.

    Equivalent to resize-short-side-to-``resize_short`` (default
    ``image_size * 256 // 224``, the standard ImageNet eval recipe) followed
    by an ``image_size`` center crop: a centered square of side
    ``short * image_size / resize_short``.
    """
    if resize_short is None:
        resize_short = image_size * 256 // 224
    short = min(width, height)
    side = max(1, int(round(short * image_size / resize_short)))
    return (width - side) // 2, (height - side) // 2, side, side


class FolderDataset:
    """ImageFolder-equivalent dataset over a ``root/<class>/<image>`` tree.

    Reference parity (SURVEY.md §2a #3, §7 hard part (a)): the reference's
    ImageNet path is ``torchvision.datasets.ImageFolder`` + RandomResizedCrop/
    flip (train) or Resize(256)/CenterCrop(224) (eval). Class names are the
    sorted subdirectory names; labels are their indices.

    Decode path: PIL with JPEG ``draft`` mode — libjpeg's DCT-space 1/2, 1/4,
    1/8 downscale — so a 224px crop from a large JPEG decodes at roughly crop
    resolution instead of full resolution, then one fused crop+bilinear-resize
    (``Image.resize(box=...)``). The C++ engine implements the same pipeline
    natively (native/batch_engine.cc jpeg mode) for GIL-free threaded decode;
    ``jpeg_paths``/``labels`` expose what it needs.
    """

    IMG_EXTS = (".jpg", ".jpeg", ".png", ".bmp", ".webp")
    mean = IMAGENET_MEAN
    std = IMAGENET_STD

    def __init__(self, root: str, train: bool = True, image_size: int = 224,
                 augment: bool | None = None, seed: int = 0):
        self.root = root
        self.image_size = image_size
        self.augment = train if augment is None else augment
        self.seed = seed
        self.epoch = 0
        self.classes = sorted(
            d for d in os.listdir(root)
            if os.path.isdir(os.path.join(root, d)) and not d.startswith("."))
        if not self.classes:
            raise FileNotFoundError(f"no class directories under {root!r}")
        self.class_to_idx = {c: i for i, c in enumerate(self.classes)}
        paths, labels = [], []
        for c in self.classes:
            cdir = os.path.join(root, c)
            for f in sorted(os.listdir(cdir)):
                if f.lower().endswith(self.IMG_EXTS):
                    paths.append(os.path.join(cdir, f))
                    labels.append(self.class_to_idx[c])
        if not paths:
            raise FileNotFoundError(f"no images under {root!r}")
        self.jpeg_paths = paths
        self.labels = np.asarray(labels, np.int32)

    def __len__(self):
        return len(self.jpeg_paths)

    def _crop_box(self, i: int, width: int, height: int):
        if self.augment:
            rng = np.random.default_rng((self.seed, self.epoch, i))
            x, y, w, h = random_resized_crop_params(rng, width, height)
            flip = bool(rng.random() < 0.5)
        else:
            x, y, w, h = center_crop_box(width, height, self.image_size)
            flip = False
        return x, y, w, h, flip

    def __getitem__(self, i: int):
        from PIL import Image

        s = self.image_size
        with Image.open(self.jpeg_paths[i]) as img:
            w0, h0 = img.size
            x, y, w, h, flip = self._crop_box(i, w0, h0)
            # DCT-scaled decode: ask for a size where the crop is >= s px.
            img.draft("RGB", (max(1, -(-w0 * s // w)), max(1, -(-h0 * s // h))))
            wd, hd = img.size
            if img.mode != "RGB":
                img = img.convert("RGB")
            sx, sy = wd / w0, hd / h0
            box = (x * sx, y * sy, (x + w) * sx, (y + h) * sy)
            img = img.resize((s, s), Image.BILINEAR, box=box)
            arr = np.asarray(img, np.uint8)
        if flip:
            arr = arr[:, ::-1]
        out = arr.astype(np.float32) / 255.0
        out = (out - IMAGENET_MEAN) / IMAGENET_STD
        return {"image": out, "label": self.labels[i]}


class SyntheticTokenDataset:
    """Fake LM sequences for GPT-2 / Llama configs: next-token prediction."""

    def __init__(self, num_examples: int = 8192, seq_len: int = 1024,
                 vocab_size: int = 50257, seed: int = 0):
        self.num_examples = num_examples
        self.seq_len = seq_len
        self.vocab_size = vocab_size
        self.seed = seed

    def __len__(self):
        return self.num_examples

    def __getitem__(self, i: int):
        rng = np.random.default_rng((self.seed, i))
        toks = rng.integers(0, self.vocab_size, self.seq_len + 1, dtype=np.int32)
        return {"tokens": toks[:-1], "targets": toks[1:]}


class TokenFileDataset:
    """LM dataset over a flat binary token file (uint16/uint32 memmap, GPT-2 style)."""

    def __init__(self, path: str, seq_len: int = 1024, dtype=np.uint16):
        self.tokens = np.memmap(path, dtype=dtype, mode="r")
        self.seq_len = seq_len

    def __len__(self):
        return (len(self.tokens) - 1) // self.seq_len

    def __getitem__(self, i: int):
        s = i * self.seq_len
        chunk = np.asarray(self.tokens[s : s + self.seq_len + 1], np.int32)
        return {"tokens": chunk[:-1], "targets": chunk[1:]}


def build_dataset(name: str, data_path: str | None, train: bool, *,
                  image_size: int = 224, seq_len: int = 1024, seed: int = 0,
                  vocab_size: int = 50257, require_split: bool = False):
    """Dataset factory used by main.py; falls back to synthetic when no data dir.

    ``require_split=True`` (eval-only mode) refuses the train-images fallback
    when ``val/`` is missing — scoring the training set must never be
    reported as "the evaluation metric" silently (ADVICE r2).
    """
    name = name.lower()
    if name == "cifar10":
        if data_path and os.path.isdir(os.path.join(data_path, "cifar-10-batches-py")):
            return CIFAR10(data_path, train=train, seed=seed)
        # Train split augments (CIFAR10-class parity); eval draws a
        # DISJOINT noise stream — genuinely held-out samples of the same
        # pattern distribution (see SyntheticImageDataset.noise_seed).
        if train:
            return SyntheticImageDataset(51200, 32, 10, seed, augment=True)
        return SyntheticImageDataset(10000, 32, 10, seed,
                                     noise_seed=seed + 777)
    if name in ("imagenet", "imagenet1k"):
        if data_path:
            split = os.path.join(data_path, "train" if train else "val")
            if os.path.isdir(split):
                root = split
            elif os.path.isdir(data_path):
                # Flat tree (class dirs at the root) or a missing val/
                # split: fall back to the usable train images — loudly,
                # because for eval that means scoring on training data.
                train_split = os.path.join(data_path, "train")
                root = (train_split
                        if not train and os.path.isdir(train_split)
                        else data_path)
                if not train and require_split and root == train_split:
                    # Only the TRAIN-IMAGES fallback is refused; a flat tree
                    # (class dirs at the root, e.g. --data-path .../val
                    # pointing straight at the eval split) stays valid.
                    raise FileNotFoundError(
                        f"--evaluate: no val/ split under {data_path!r} — "
                        "refusing to score the training images as the "
                        "evaluation metric")
                if not train:
                    import logging

                    logging.getLogger(__name__).warning(
                        "no val/ split under %r; evaluation will run on "
                        "the SAME images as training", data_path)
            else:
                raise FileNotFoundError(
                    f"--data-path {data_path!r} does not exist")
            return FolderDataset(root, train=train, image_size=image_size,
                                 seed=seed)
        # perf vehicle (no augment), but eval still gets a disjoint
        # noise stream so synthetic "val" never scores seen samples
        return SyntheticImageDataset(
            1281167 if train else 50000, image_size, 1000, seed,
            noise_seed=seed if train else seed + 777)
    if name in ("lm", "synthetic_lm", "openwebtext"):
        if data_path and os.path.isfile(data_path):
            return TokenFileDataset(data_path, seq_len=seq_len)
        return SyntheticTokenDataset(seq_len=seq_len, seed=seed,
                                     vocab_size=vocab_size)
    raise ValueError(f"unknown dataset {name!r}")
