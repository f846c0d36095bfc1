"""Dataset classes — host-side image pipeline (replaces diffusion_loader.py).

All three reference datasets are provided with their exact tensor contracts
``__getitem__(index, t=None) → (noisy, target, t)`` where images are float32
HWC in [−1, 1] (NHWC is the TPU-native layout; the torch reference is CHW).

Reference quirks fixed per SURVEY.md's quirks register (do-not-copy list):
 #1 ``ColdDownSampleDataset`` defines ``__len__`` (upstream omits it and would
    crash DistributedSampler, diffusion_loader.py:60-97 vs :137-138);
 #2 the index is honored — upstream ``DiffusionDataset`` overrides it with
    ``random.randint(0,9)`` (diffusion_loader.py:44), a debug leftover.
File listings are sorted for cross-host determinism (upstream relies on raw
``os.listdir`` order, which is filesystem-dependent — under SPMD every host
must agree on the index→file mapping).

Per-item randomness (the step t, the Gaussian noise) is drawn from a
``seed/epoch/index``-keyed generator so any sample is reproducible — upstream
leaves this to worker-process global RNG state.

Decoded-image caching: the reference re-decodes every jpg every epoch
(diffusion_loader.py:47 via DataLoader workers); at TPU step rates the decode
dominates the epoch. Both datasets therefore cache the decoded+resized base
image (the deterministic part — corruption stays per-epoch random) in RAM,
auto-enabled when the whole dataset fits ``CACHE_BUDGET_BYTES`` and
overridable via ``cache_images``/the YAML ``cache_images`` key.
"""

from __future__ import annotations

import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np
from PIL import Image

from ddim_cold_tpu.data import native, resize
from ddim_cold_tpu.obs import spans

_IMG_EXTS = {".jpg", ".jpeg", ".png", ".bmp", ".webp"}

#: auto-enable the decoded-image cache while all caching datasets in the
#: process together fit in this budget (train + val both auto-enable)
CACHE_BUDGET_BYTES = 2 << 30
#: skip the uint8 header probe (→ float32 mode) above this many files — the
#: per-file header open would dominate startup on huge/network datasets
U8_PROBE_MAX_FILES = 100_000
_cache_reserved = 0
_cache_lock = threading.Lock()


class _BaseCache:
    """Decoded-base-image cache shared by both dataset classes.

    Entries are keyed by index and stored RAW-preferred: uint8 RGB when the
    file decodes at exactly ``img_size`` (no resize — 4× less RAM, and the
    uint8 transfer path ships these bytes straight to the device), float32
    HWC [−1,1] otherwise. ``_normalize`` converts on read with the exact
    host-pipeline op order, so both storage forms are interchangeable.
    Concurrent ``__getitem__`` calls may race on a miss — both decode, one
    write wins; contents are identical either way (native and PIL paths are
    bit-exact, tests/test_native).
    """

    def _probe_uniform_u8(self) -> bool:
        """Header-only size scan (no pixel decode): True when EVERY file's
        native size equals img_size, i.e. raw uint8 storage/transfer applies.

        The decision is per-dataset, never per-batch — batch dtype must be
        stable across batches and across SPMD hosts (every host lists the
        same sorted files AND checks the same native capability, so every
        host with an identical build decides identically). u8 entries only
        ever come from the native decode tier, so the mode requires the
        ``ddim_decode_batch`` entry point — a stale .so forces float32
        everywhere rather than diverging from the budget estimate.

        Cost control: the first header short-circuits resize-needed datasets
        instantly; homogeneous datasets scan the rest over a thread pool;
        above U8_PROBE_MAX_FILES the probe is skipped (float32 mode) so a
        million-file dataset never serializes header reads into startup."""
        if not (self.use_native and native.has_decode_batch()):
            return False
        if len(self.imgList) > U8_PROBE_MAX_FILES:
            return False
        want = (int(self.img_size[1]), int(self.img_size[0]))  # PIL is (w, h)

        def ok(name: str) -> bool:
            try:
                with Image.open(os.path.join(self.root, name)) as im:
                    return im.size == want
            except Exception:  # noqa: BLE001 — PIL decode errors are legion; any failure just means "probe says no"
                return False

        if not ok(self.imgList[0]):
            return False
        # chunked scan: a mismatch bails after its chunk — an eager full
        # pool.map would submit (and then wait out) every remaining open
        with ThreadPoolExecutor(8) as pool:
            for lo in range(1, len(self.imgList), 1024):
                if not all(pool.map(ok, self.imgList[lo:lo + 1024])):
                    return False
        return True

    def _open(self, cache_images: Optional[bool]) -> None:
        """What a constructor does on disk — the file listing, the header
        probe (which loads, and on a checkout's first use builds, the native
        decoder) and the cache's reservation — as one ``data/dataset/open``
        layer span (``images``, ``cached``) an object."""
        with spans.layer("data/dataset/open") as span:
            self.imgList = _list_images(self.root,
                                        hint_size=int(self.img_size[0]))
            self._init_cache(cache_images, len(self.imgList), self.img_size)
            span.set(images=len(self.imgList), cached=self.cache_images)

    def _init_cache(self, cache_images: Optional[bool], n_items: int,
                    img_size: Sequence[int]) -> None:
        global _cache_reserved
        self._uniform_u8 = self._probe_uniform_u8()
        # uint8 entries are 4× smaller — let the auto budget see that
        est = n_items * int(img_size[0]) * int(img_size[1]) * 3 * (
            1 if self._uniform_u8 else 4)
        if cache_images is None:
            # budget is process-wide: train + val datasets both auto-enabling
            # must together stay under CACHE_BUDGET_BYTES
            with _cache_lock:
                cache_images = _cache_reserved + est <= CACHE_BUDGET_BYTES
                if cache_images:
                    _cache_reserved += est
        elif cache_images:
            with _cache_lock:
                _cache_reserved += est
        self.cache_images = bool(cache_images)
        self._cache_reservation = est if self.cache_images else 0
        self._cache: dict[int, np.ndarray] = {}

    def __del__(self):
        res = getattr(self, "_cache_reservation", 0)
        if res:
            try:
                global _cache_reserved
                with _cache_lock:
                    _cache_reserved -= res
            except Exception:  # noqa: BLE001 — interpreter teardown: globals may be gone
                pass

    @staticmethod
    def _normalize(entry: np.ndarray) -> np.ndarray:
        """uint8 entry → float32 [−1,1] with the exact ``_load_base`` op order
        (÷255 then ·2−1); float entries pass through."""
        if entry.dtype == np.uint8:
            return (entry.astype(np.float32) / 255.0) * 2.0 - 1.0
        return entry

    def _load_raw(self, path: str) -> np.ndarray:
        """One file, raw-preferred: uint8 when it decodes at exactly img_size,
        else the float [−1,1] resize pipeline."""
        img = pil_loader(path)
        if (img.height, img.width) == tuple(self.img_size):
            return np.asarray(img, dtype=np.uint8)
        arr = np.asarray(img, dtype=np.float32) / 255.0
        return resize.resize_bilinear(arr, tuple(self.img_size)) * 2.0 - 1.0

    def _base(self, index: int) -> np.ndarray:
        """Decoded+resized float32 base image for one item, through the cache."""
        hit = self._cache.get(index) if self.cache_images else None
        if hit is not None:
            return self._normalize(hit)
        if self.use_native:
            raw = self._raw_entries([index], num_threads=1)
            return self._normalize(raw[0])
        img = _load_base(os.path.join(self.root, self.imgList[index]),
                         self.img_size, use_native=False)
        if self.cache_images:
            self._cache[index] = img
        return img

    def _raw_entries(self, indices: Sequence[int], num_threads: int,
                     pool=None) -> list[np.ndarray]:
        """Cache entries (u8 or f32, see class docstring) for a batch.

        Misses fill in three tiers: raw C++ u8 decode (exact-size files) →
        fused C++ f32 decode+resize (size-mismatched files) → PIL per item
        (formats native rejects), fanned over ``pool`` when provided.
        """
        missing = ([i for i in indices if int(i) not in self._cache]
                   if self.cache_images else list(indices))
        got: dict[int, np.ndarray] = {}
        if missing:
            paths = [os.path.join(self.root, self.imgList[int(i)]) for i in missing]
            if self._uniform_u8:  # gated by the header probe — a dataset that
                # needs resizing must not pay a doomed full decode here
                res = native.decode_batch(paths, self.img_size,
                                          num_threads=num_threads)
                if res is not None:
                    u8, failed = res
                    for j, i in enumerate(missing):
                        if not failed[j]:
                            got[int(i)] = u8[j]
            left = [(j, int(i)) for j, i in enumerate(missing) if int(i) not in got]
            if left and not self._uniform_u8:
                # f32 fused decode+resize — NEVER under u8 mode: a runtime
                # decode failure must not flip the pinned batch dtype (PIL
                # below returns u8 for exact-size files, keeping the invariant)
                res = native.base_batch([paths[j] for j, _ in left],
                                        self.img_size, num_threads=num_threads)
                if res is not None:
                    f32, failed = res
                    for k, (_, i) in enumerate(left):
                        if not failed[k]:
                            got[i] = f32[k]
                left = [(j, i) for j, i in left if i not in got]
            if left:  # formats native rejects (progressive jpg/webp/…) → PIL
                mapper = pool.map if pool is not None else map
                for (j, i), entry in zip(
                    left, mapper(self._load_raw, [paths[j] for j, _ in left])
                ):
                    got[i] = entry
            if self.cache_images:
                # .copy(): u8[j]/f32[k] are views into the batch buffers —
                # caching views would pin the whole buffer per entry
                self._cache.update({k: v.copy() for k, v in got.items()})
        if self.cache_images:
            return [self._cache[int(i)] for i in indices]
        return [got[int(i)] for i in indices]  # no cache → all were missing

    def _raw_bases(self, indices: Sequence[int], num_threads: int,
                   pool=None) -> np.ndarray:
        """Stacked bases for the device-corruption path, dtype pinned
        per-DATASET (_uniform_u8): uint8 raw bytes for uniform datasets,
        float32 [−1,1] otherwise. The single place the pinning is enforced —
        both datasets' get_raw_batch delegate here."""
        if self.use_native:
            entries = self._raw_entries(indices, num_threads, pool=pool)
        else:  # per-item through the cache, fanned over the loader's pool
            mapper = pool.map if pool is not None else map
            entries = list(mapper(self._base, map(int, indices)))
        if self._uniform_u8:
            bad = [int(i) for i, e in zip(indices, entries)
                   if e.dtype != np.uint8]
            if bad:
                # never silently flip the batch dtype mid-run: it forces a jit
                # retrace, and under multi-host SPMD a single host shipping
                # float32 while the rest ship uint8 diverges the global array
                # dtype (hang/crash). Only cause: a file changed on disk after
                # the header probe pinned this dataset uint8.
                raise RuntimeError(
                    f"dataset pinned uint8 but indices {bad[:8]} decoded to a "
                    "different dtype — files mutated after the header probe; "
                    "rebuild the dataset or reopen it to re-probe")
            return np.stack(entries)
        return np.stack([self._normalize(e) for e in entries])

    def _bases_for(self, indices: Sequence[int], num_threads: int,
                   pool=None) -> np.ndarray:
        """Batch of float32 [−1,1] bases (the host-degrade contract)."""
        return np.stack([
            self._normalize(e)
            for e in self._raw_entries(indices, num_threads, pool=pool)
        ])


def pil_loader(path: str) -> Image.Image:
    """Open an image file and force RGB (reference diffusion_loader.py:17-21).

    PIL is the LAST decode tier (native rejects route here), so its failures
    are terminal: re-raise with the offending path attached — a
    DecompressionBombError or truncated-file error naming only an internal
    buffer is undebuggable mid-epoch over a million-file dataset."""
    with open(path, "rb") as f:
        try:
            img = Image.open(f)
            return img.convert("RGB")
        except Exception as e:  # noqa: BLE001 — re-raised below with the path attached
            # prepend the path in-place: constructing type(e) from a bare
            # string is not a safe contract across exception classes
            e.args = (f"{path}: " + (str(e.args[0]) if e.args else repr(e)),
                      *e.args[1:])
            raise


def _list_images(root: str, hint_size: int = 64) -> list[str]:
    if not os.path.isdir(root):
        out = os.path.dirname(root) or root  # <set>/train → <set>
        raise FileNotFoundError(
            f"dataset folder {root!r} does not exist — point the yaml's "
            "dataStorage at a folder of images, or generate the committed "
            f"surrogate set: python scripts/make_dataset.py --out {out} "
            f"--size {hint_size}")
    names = sorted(
        n for n in os.listdir(root) if os.path.splitext(n)[1].lower() in _IMG_EXTS
    )
    if not names:
        raise FileNotFoundError(f"no image files in {root!r}")
    return names


def _load_base(path: str, img_size: Sequence[int], use_native: bool = True) -> np.ndarray:
    """jpg → float32 HWC in [−1, 1]: to_tensor (÷255) → bilinear resize →
    ·2−1 (reference diffusion_loader.py:47-49 order).

    Dispatches to the native C++ decoder (data/native.py) when available —
    same math, same output, no GIL; falls back to PIL/numpy per-file.
    """
    hw = (int(img_size[0]), int(img_size[1]))
    if use_native:
        out = native.load_base(path, hw)
        if out is not None:
            return out
    img = np.asarray(pil_loader(path), dtype=np.float32) / 255.0
    img = resize.resize_bilinear(img, hw)
    return img * 2.0 - 1.0


class DiffusionDataset(_BaseCache):
    """Gaussian forward-noising dataset (reference diffusion_loader.py:24-58).

    ``__getitem__ → (x_t, x_0, t)`` with t ~ U[0, max_step) and
    x_t = √ᾱ·x0 + √(1−ᾱ)·ε under ᾱ = 1 − √((t+1)/T).
    """

    def __init__(self, root: str, imgSize: Sequence[int] = (32, 32), max_step: int = 2000,
                 seed: int = 0, use_native: bool = True,
                 cache_images: Optional[bool] = None):
        self.root = root
        self.img_size = tuple(int(s) for s in imgSize)
        self.max_step = max_step
        self.seed = seed
        self.use_native = use_native
        self.epoch = 0
        self._open(cache_images)

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def _rng(self, index: int) -> np.random.Generator:
        return np.random.Generator(
            np.random.Philox(np.random.SeedSequence([self.seed, self.epoch, index, 0xD1FF]))
        )

    def _noise_for(self, index: int, img: np.ndarray, t: Optional[int]):
        """(t, x_t) from the per-(seed, epoch, index) Philox stream — t is
        drawn BEFORE the noise, so native/PIL decode paths see identical
        randomness."""
        rng = self._rng(index)
        drawn = int(rng.integers(self.max_step))
        if t is None:
            t = drawn
        alpha = 1.0 - math.sqrt((t + 1) / self.max_step)
        noise = rng.standard_normal(img.shape).astype(np.float32)
        noisy = math.sqrt(alpha) * img + math.sqrt(1.0 - alpha) * noise
        return t, noisy.astype(np.float32)

    def __getitem__(self, index: int, t: Optional[int] = None):
        img = self._base(index)
        t, noisy = self._noise_for(index, img, t)
        return noisy, img.astype(np.float32), t

    def get_raw_batch(self, indices: Sequence[int], num_threads: int = 8,
                      pool=None):
        """Device-side-corruption path: ``(x₀, t)`` — clean bases (uint8 when
        the dataset is uniform at img_size, see _BaseCache) plus per-sample
        steps from the SAME Philox stream as the host path (t is drawn before
        the noise there, so schedules agree). The forward noising happens
        in-jit (ops/degrade.make_gaussian_prepare) with device-drawn ε."""
        ts = np.empty(len(indices), np.int32)
        for j, i in enumerate(indices):
            ts[j] = int(self._rng(int(i)).integers(self.max_step))
        return self._raw_bases(indices, num_threads, pool=pool), ts

    def get_batch(self, indices: Sequence[int], num_threads: int = 8,
                  pool=None):
        """Batch fast path: decode+resize in C++ threads (through the cache),
        noise in numpy. Returns collated ``(noisy, target, t)`` arrays, or
        None to make the loader fall back to per-item assembly.
        ``pool`` fans the PIL tier (formats native rejects) over the loader's
        shared executor."""
        if not self.use_native:
            return None
        base = self._bases_for(indices, num_threads, pool=pool)
        noisy = np.empty_like(base)
        ts = np.empty(len(base), np.int32)
        for j, i in enumerate(indices):
            ts[j], noisy[j] = self._noise_for(int(i), base[j], None)
        return noisy, base, ts

    def __len__(self) -> int:
        return len(self.imgList)


class ColdDownSampleDataset(_BaseCache):
    """Cold (downsampling) degradation dataset (reference diffusion_loader.py:60-138).

    ``target_mode``:
      * ``"chain"`` (default — what the trainer uses, multi_gpu_trainer.py:5,59):
        returns ``(D(x,t), D(x,t−1), t)`` — one-level restoration targets.
      * ``"direct"`` (the ``_au`` paper variant, diffusion_loader.py:99-138):
        returns ``(D(x,t), x_0, t)`` — direct clean-image targets.

    max_step = log2(size) (6 for 64px); t ∈ [1, max_step]; the degradation is
    nearest-resize down to ⌊size/2^t⌋ then nearest back up, torch interpolate
    index convention (data/resize.py).
    """

    def __init__(self, root: str, imgSize: Sequence[int] = (32, 32),
                 target_mode: str = "chain", seed: int = 0, use_native: bool = True,
                 cache_images: Optional[bool] = None):
        if imgSize[0] != imgSize[1]:
            raise ValueError("downsample dataset requires square images")
        if target_mode not in ("chain", "direct"):
            raise ValueError(f"unknown target_mode {target_mode!r}")
        self.root = root
        self.img_size = tuple(int(s) for s in imgSize)
        self.size = int(imgSize[0])
        self.max_step = int(np.log2(self.size))
        self.target_mode = target_mode
        self.seed = seed
        self.use_native = use_native
        self.epoch = 0
        self._open(cache_images)

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def get_t(self, img: np.ndarray, level_scale: int) -> np.ndarray:
        """D(x, s) for s = 2^t (reference diffusion_loader.py:79-83)."""
        return resize.cold_degrade(img, level_scale, self.size)

    def _draw_t(self, index: int) -> int:
        rng = np.random.Generator(
            np.random.Philox(np.random.SeedSequence([self.seed, self.epoch, index, 0xC01D]))
        )
        return int(rng.integers(self.max_step)) + 1  # t ∈ [1, max_step]

    def _degrade_pair(self, img: np.ndarray, t: int):
        """(D(x,t), target) from a decoded base image (numpy nearest-resize)."""
        noisy_t = self.get_t(img, 2**t)
        target = self.get_t(img, 2 ** (t - 1)) if self.target_mode == "chain" else img
        return noisy_t.astype(np.float32), target.astype(np.float32)

    def __getitem__(self, index: int, t: Optional[int] = None):
        path = os.path.join(self.root, self.imgList[index])
        if t is None:
            t = self._draw_t(index)
        if self.cache_images:
            # cached base + numpy degrade (degrade is cheap; decode was the cost)
            noisy, target = self._degrade_pair(self._base(index), t)
            return noisy, target, t
        if self.use_native:
            # full item (decode → resize → degrade) in one C++ call
            res = native.cold_item(path, self.size, t, self.target_mode == "chain")
            if res is not None:
                return res[0], res[1], t
        return self._pil_item(index, t)

    def get_batch(self, indices: Sequence[int], num_threads: int = 8,
                  pool=None):
        """Batch fast path: the whole (decode, resize, degrade, collate)
        pipeline in C++ threads (decode through the cache when enabled);
        failed slots redone via PIL with the same t. Returns
        ``(noisy, target, t)`` or None (→ loader per-item path).
        ``pool`` fans the PIL tier over the loader's shared executor."""
        if not self.use_native:
            return None
        ts = [self._draw_t(int(i)) for i in indices]
        if self.cache_images:
            base = self._bases_for(indices, num_threads, pool=pool)
            pair = native.cold_pair_batch(base, ts, self.target_mode == "chain",
                                          num_threads=num_threads)
            if pair is not None:
                return pair[0], pair[1], np.asarray(ts, np.int32)
            pairs = [self._degrade_pair(base[j], ts[j]) for j in range(len(ts))]
            return (np.stack([p[0] for p in pairs]),
                    np.stack([p[1] for p in pairs]),
                    np.asarray(ts, np.int32))
        paths = [os.path.join(self.root, self.imgList[int(i)]) for i in indices]
        res = native.cold_batch(paths, ts, self.size, self.target_mode == "chain",
                                num_threads=num_threads)
        if res is None:
            return None
        noisy, target, failed = res
        if failed.all():
            # fully non-native batch → loader's parallel per-item path
            return None
        for j, i in enumerate(indices):
            if failed[j]:
                noisy[j], target[j], _ = self._pil_item(int(i), ts[j])
        return noisy, target, np.asarray(ts, np.int32)

    def get_raw_batch(self, indices: Sequence[int], num_threads: int = 8,
                      pool=None):
        """Device-side-corruption path: ``(base, t)`` — the clean decoded
        bases plus the per-sample steps, with NO host degradation. The jitted
        step rebuilds ``(D(x,t), target, t)`` on device via
        ops/degrade.make_cold_prepare (bit-identical gathers), so the host
        ships one image per sample instead of two degraded copies — the
        transfer, not the decode, dominates on network-attached TPU hosts.

        ``t`` comes from the same per-(seed, epoch, index) stream as the host
        path, so both paths train on identical corruption schedules.
        ``pool`` is the loader's shared ThreadPoolExecutor for the PIL
        fallback (avoids per-batch executor churn on the hot path).

        When every base decodes at exactly img_size the batch ships as raw
        **uint8** (4× less host→device traffic than float32; the in-jit
        ``normalize_base`` conversion is bit-exact), else float32."""
        ts = np.asarray([self._draw_t(int(i)) for i in indices], np.int32)
        return self._raw_bases(indices, num_threads, pool=pool), ts

    def _pil_item(self, index: int, t: int):
        img = _load_base(os.path.join(self.root, self.imgList[index]),
                         self.img_size, use_native=False)
        noisy_t = self.get_t(img, 2**t)
        target = self.get_t(img, 2 ** (t - 1)) if self.target_mode == "chain" else img
        return noisy_t.astype(np.float32), target.astype(np.float32), t

    def __len__(self) -> int:
        return len(self.imgList)
