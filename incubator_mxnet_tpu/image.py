"""Image pipeline: decode → augment → batch → prefetch.

Reference: `src/io/iter_image_recordio_2.cc` (ImageRecordIter),
`image_aug_default.cc` (augmenters: resize, random-resized-crop, mirror,
HSL jitter), python surface `python/mxnet/image/image.py` (ImageIter,
CreateAugmenter).  Decode uses PIL (no OpenCV in this environment — the C++
decode pool lands with the native IO module in `src/`); the threaded
prefetcher overlaps host decode with device compute, and `part_index/
num_parts` sharding matches the reference's multi-worker input splitting.
"""
from __future__ import annotations

import ctypes
import os
import random as _pyrandom
import threading
import queue as _queue

import numpy as np

from .analysis import locks as _alocks

from .base import MXNetError
from .io import DataIter, DataBatch, DataDesc
from .ndarray.ndarray import NDArray, array
from . import native as _native
from . import recordio as _recordio


# ---------------------------------------------------------------------------
# numpy augmenter primitives (reference image_aug_default.cc)
# ---------------------------------------------------------------------------

def imdecode(buf, to_rgb=1, **kwargs):
    """Decode image bytes to NDArray HWC (reference `image_io.cc imdecode`)."""
    import io as _io
    from PIL import Image
    img = Image.open(_io.BytesIO(buf))
    img = img.convert("RGB" if to_rgb else "BGR")
    return array(np.asarray(img, dtype=np.uint8), dtype="uint8")


def _resize_np(img, w, h, interp=2):
    from PIL import Image
    return np.asarray(Image.fromarray(img).resize((w, h), Image.BILINEAR))


def resize_short(src, size, interp=2):
    """Resize shorter edge to size (reference `image.py resize_short`)."""
    img = src.asnumpy() if isinstance(src, NDArray) else src
    h, w = img.shape[:2]
    if h > w:
        new_w, new_h = size, int(h * size / w)
    else:
        new_w, new_h = int(w * size / h), size
    return array(_resize_np(img, new_w, new_h), dtype="uint8")


def center_crop(src, size, interp=2):
    img = src.asnumpy() if isinstance(src, NDArray) else src
    h, w = img.shape[:2]
    cw, ch = size
    x0 = max((w - cw) // 2, 0)
    y0 = max((h - ch) // 2, 0)
    out = img[y0:y0 + ch, x0:x0 + cw]
    if out.shape[:2] != (ch, cw):
        out = _resize_np(out, cw, ch)
    return array(out, dtype="uint8"), (x0, y0, cw, ch)


def random_crop(src, size, interp=2):
    img = src.asnumpy() if isinstance(src, NDArray) else src
    h, w = img.shape[:2]
    cw, ch = size
    if w < cw or h < ch:
        img = _resize_np(img, max(w, cw), max(h, ch))
        h, w = img.shape[:2]
    x0 = _pyrandom.randint(0, w - cw)
    y0 = _pyrandom.randint(0, h - ch)
    return array(img[y0:y0 + ch, x0:x0 + cw], dtype="uint8"), (x0, y0, cw, ch)


def random_size_crop(src, size, area, ratio, interp=2):
    """Random-resized-crop (reference image_aug_default.cc / image.py)."""
    img = src.asnumpy() if isinstance(src, NDArray) else src
    h, w = img.shape[:2]
    src_area = h * w
    if isinstance(area, (int, float)):
        area = (area, 1.0)
    for _ in range(10):
        target_area = _pyrandom.uniform(*area) * src_area
        log_ratio = (np.log(ratio[0]), np.log(ratio[1]))
        aspect = np.exp(_pyrandom.uniform(*log_ratio))
        cw = int(round(np.sqrt(target_area * aspect)))
        ch = int(round(np.sqrt(target_area / aspect)))
        if cw <= w and ch <= h:
            x0 = _pyrandom.randint(0, w - cw)
            y0 = _pyrandom.randint(0, h - ch)
            crop = img[y0:y0 + ch, x0:x0 + cw]
            return array(_resize_np(crop, size[0], size[1]), dtype="uint8"), \
                (x0, y0, cw, ch)
    return center_crop(array(_resize_np(img, size[0], size[1]), dtype="uint8"),
                       size)


class Augmenter:
    """Base augmenter (reference `image.py:Augmenter`)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def dumps(self):
        import json
        return json.dumps([self.__class__.__name__.lower(), self._kwargs],
                          default=lambda o: o.tolist()
                          if hasattr(o, "tolist") else str(o))

    def __call__(self, src):
        raise NotImplementedError


class ResizeAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size

    def __call__(self, src):
        return resize_short(src, self.size)


class ForceResizeAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size

    def __call__(self, src):
        img = src.asnumpy() if isinstance(src, NDArray) else src
        return array(_resize_np(img, self.size[0], self.size[1]), dtype="uint8")


class RandomCropAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size

    def __call__(self, src):
        return random_crop(src, self.size)[0]


class CenterCropAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size

    def __call__(self, src):
        return center_crop(src, self.size)[0]


class RandomSizedCropAug(Augmenter):
    def __init__(self, size, area, ratio, interp=2):
        super().__init__(size=size, area=area, ratio=ratio, interp=interp)
        self.size = size
        self.area = area
        self.ratio = ratio

    def __call__(self, src):
        return random_size_crop(src, self.size, self.area, self.ratio)[0]


class HorizontalFlipAug(Augmenter):
    def __init__(self, p):
        super().__init__(p=p)
        self.p = p

    def __call__(self, src):
        if _pyrandom.random() < self.p:
            img = src.asnumpy() if isinstance(src, NDArray) else src
            return array(img[:, ::-1].copy(), dtype="uint8")
        return src


class BrightnessJitterAug(Augmenter):
    def __init__(self, brightness):
        super().__init__(brightness=brightness)
        self.brightness = brightness

    def __call__(self, src):
        alpha = 1.0 + _pyrandom.uniform(-self.brightness, self.brightness)
        img = (src.asnumpy().astype("float32") * alpha).clip(0, 255)
        return array(img.astype("uint8"), dtype="uint8")


class ColorNormalizeAug(Augmenter):
    def __init__(self, mean, std):
        super().__init__(mean=mean, std=std)
        self.mean = np.asarray(mean, dtype="float32") if mean is not None else None
        self.std = np.asarray(std, dtype="float32") if std is not None else None

    def __call__(self, src):
        img = src.asnumpy().astype("float32") if isinstance(src, NDArray) else \
            src.astype("float32")
        if self.mean is not None:
            img = img - self.mean
        if self.std is not None:
            img = img / self.std
        return array(img, dtype="float32")


class CastAug(Augmenter):
    def __call__(self, src):
        return array(src.asnumpy().astype("float32"), dtype="float32")


def CreateAugmenter(data_shape, resize=0, rand_crop=False, rand_resize=False,
                    rand_mirror=False, mean=None, std=None, brightness=0,
                    contrast=0, saturation=0, hue=0, pca_noise=0,
                    rand_gray=0, inter_method=2):
    """Reference `image.py CreateAugmenter`."""
    auglist = []
    if resize > 0:
        auglist.append(ResizeAug(resize, inter_method))
    crop_size = (data_shape[2], data_shape[1])
    if rand_resize:
        auglist.append(RandomSizedCropAug(crop_size, (0.08, 1.0),
                                          (3 / 4.0, 4 / 3.0), inter_method))
    elif rand_crop:
        auglist.append(RandomCropAug(crop_size, inter_method))
    else:
        auglist.append(CenterCropAug(crop_size, inter_method))
    if rand_mirror:
        auglist.append(HorizontalFlipAug(0.5))
    auglist.append(CastAug())
    if brightness:
        auglist.append(BrightnessJitterAug(brightness))
    if mean is True:
        mean = np.array([123.68, 116.28, 103.53])
    if std is True:
        std = np.array([58.395, 57.12, 57.375])
    if mean is not None or std is not None:
        auglist.append(ColorNormalizeAug(mean, std))
    return auglist


class ImageIter(DataIter):
    """Python image iterator over .rec or image list
    (reference `python/mxnet/image/image.py:ImageIter`)."""

    def __init__(self, batch_size, data_shape, label_width=1,
                 path_imgrec=None, path_imglist=None, path_root=None,
                 shuffle=False, part_index=None, num_parts=None,
                 aug_list=None, imglist=None, data_name="data",
                 label_name="softmax_label", **kwargs):
        super().__init__(batch_size)
        assert path_imgrec or path_imglist or imglist
        self.data_shape = tuple(data_shape)
        self.batch_size = batch_size
        self.label_width = label_width
        self.shuffle = shuffle
        self.auglist = aug_list if aug_list is not None else \
            CreateAugmenter(data_shape, **{k: v for k, v in kwargs.items()
                                           if k in ("resize", "rand_crop",
                                                    "rand_resize", "rand_mirror",
                                                    "mean", "std")})
        self.imgrec = None
        self.imglist = None
        self.path_root = path_root
        if path_imgrec:
            idx_path = os.path.splitext(path_imgrec)[0] + ".idx"
            if os.path.exists(idx_path):
                self.imgrec = _recordio.MXIndexedRecordIO(idx_path, path_imgrec,
                                                          "r")
                self.seq = list(self.imgrec.keys)
            else:
                self.imgrec = _recordio.MXRecordIO(path_imgrec, "r")
                self.seq = None
        elif path_imglist:
            with open(path_imglist) as fin:
                imglist = {}
                for line in fin:
                    parts = line.strip().split("\t")
                    label = np.asarray(parts[1:-1], dtype="float32")
                    imglist[int(parts[0])] = (label, parts[-1])
                self.imglist = imglist
                self.seq = list(imglist.keys())
        else:
            self.imglist = {i: (np.asarray(l, dtype="float32"), p)
                            for i, (l, p) in enumerate(imglist)}
            self.seq = list(self.imglist.keys())
        # per-host sharding over the sequence: `recordio.shard_range`
        # (disjoint/exhaustive — the old `len//num_parts` slice silently
        # DROPPED the remainder records); num_parts=None/'auto' resolves
        # from the dist environment, re-checked at reset() so a shrunk
        # pod re-shards on the epoch fence
        self._full_seq = list(self.seq) if self.seq is not None else None
        self._part_index_req = part_index
        self._num_parts_req = num_parts
        self._quarantined_ids = set()
        self._reshard_seq()
        self.cur = 0
        self.data_name = data_name
        self.label_name = label_name
        self.corrupt_records = 0   # undecodable/corrupt samples skipped
        self._quarantine = None
        self.reset()

    def set_quarantine(self, log):
        """Attach a quarantine log (resilience.guardian.QuarantineLog):
        corrupt samples this iterator skips append one entry each, and
        the underlying RecordIO reader's structural skips do too."""
        self._quarantine = log
        if self.imgrec is not None and hasattr(self.imgrec,
                                               "set_quarantine"):
            self.imgrec.set_quarantine(log)

    def apply_quarantine(self, entries):
        """Drop records previously quarantined for this source (resume
        path): their ids never enter the epoch sequence again — held on
        the quarantine set so an epoch-fence re-shard cannot resurrect
        them."""
        if self.seq is None:
            return
        bad = {int(e["record"]) for e in entries
               if e.get("record") is not None and e.get("source") in (
                   None, getattr(self.imgrec, "uri", None))}
        if bad:
            self._quarantined_ids.update(bad)
            self.seq = [k for k in self.seq if k not in bad]

    def _reshard_seq(self):
        """This epoch's sequence from the full list: the resolved shard
        window (`recordio.shard_range`) minus quarantined ids."""
        if self._full_seq is None:
            return
        pi, nparts = self._part_index_req, self._num_parts_req
        if nparts == "auto":
            # explicit opt-in only (an unset num_parts must not shard
            # eval iterators in dist runs); MXNET_IO_AUTO_SHARD=0 is
            # the ops off-switch
            from . import config as _config
            from . import io_plane as _io_plane
            if _config.get("MXNET_IO_AUTO_SHARD"):
                pi, nparts = _io_plane.auto_shard(
                    pi if pi != "auto" else None, None)
            else:
                pi, nparts = 0, 1
        elif nparts in (None, 0):
            pi, nparts = 0, 1
        lo, hi = _recordio.shard_range(len(self._full_seq), int(nparts),
                                       int(pi or 0))
        bad = self._quarantined_ids
        self.seq = [k for k in self._full_seq[lo:hi] if k not in bad]

    def _corrupt_sample(self, idx, exc):
        self.corrupt_records += 1
        import logging
        logging.getLogger(__name__).warning(
            "ImageIter: skipping corrupt record %s (%s) — "
            "corrupt_records=%d", idx, str(exc)[:120],
            self.corrupt_records)
        if self._quarantine is not None:
            try:
                self._quarantine.append(
                    reason="corrupt_record",
                    source=getattr(self.imgrec, "uri", None),
                    record=idx if isinstance(idx, int) else None,
                    detail=str(exc)[:200])
            except Exception:
                pass
        try:
            from .resilience import faults as _faults
            _faults.note("corrupt-record", site="io.corrupt_record",
                         record=idx if isinstance(idx, int) else -1)
        except Exception:
            pass

    @property
    def provide_data(self):
        return [DataDesc(self.data_name, (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self.label_width == 1 else \
            (self.batch_size, self.label_width)
        return [DataDesc(self.label_name, shape)]

    def reset(self):
        # the epoch fence: re-resolve the shard (a shrunk pod's
        # rewritten rank/world re-splits the sequence here)
        self._reshard_seq()
        if self.shuffle and self.seq is not None:
            _pyrandom.shuffle(self.seq)
        if self.imgrec is not None and self.seq is None:
            self.imgrec.reset()
        self.cur = 0

    def next_sample(self):
        self._last_idx = None
        if self.seq is not None:
            if self.cur >= len(self.seq):
                raise StopIteration
            idx = self.seq[self.cur]
            self.cur += 1
            self._last_idx = idx
            if self.imgrec is not None:
                s = self.imgrec.read_idx(idx)
                header, img = _recordio.unpack(s)
                return header.label, img
            label, fname = self.imglist[idx]
            with open(os.path.join(self.path_root or "", fname), "rb") as f:
                return label, f.read()
        s = self.imgrec.read()
        if s is None:
            raise StopIteration
        header, img = _recordio.unpack(s)
        return header.label, img

    def next(self):
        c, h, w = self.data_shape
        batch_data = np.zeros((self.batch_size, c, h, w), dtype="float32")
        batch_label = np.zeros((self.batch_size, self.label_width), dtype="float32")
        i = 0
        pad = 0
        try:
            while i < self.batch_size:
                try:
                    label, buf = self.next_sample()
                    img = imdecode(buf)
                except StopIteration:
                    raise
                except Exception as e:
                    # a corrupt record (torn payload, bit-flipped JPEG,
                    # bad header) must not kill the epoch: skip it with
                    # a counted warning and feed the quarantine log
                    self._corrupt_sample(self._last_idx, e)
                    continue
                for aug in self.auglist:
                    img = aug(img)
                arr = img.asnumpy()
                batch_data[i] = arr.transpose(2, 0, 1)
                lab = np.asarray(label, dtype="float32").reshape(-1)
                batch_label[i, :len(lab[:self.label_width])] = \
                    lab[:self.label_width]
                i += 1
        except StopIteration:
            if i == 0:
                raise
            pad = self.batch_size - i
        label_out = batch_label[:, 0] if self.label_width == 1 else batch_label
        return DataBatch(data=[array(batch_data)], label=[array(label_out)],
                         pad=pad, provide_data=self.provide_data,
                         provide_label=self.provide_label)


class ImageRecordIterImpl(DataIter):
    """Param-compatible `ImageRecordIter` (reference
    `iter_image_recordio_2.cc:727` registration).

    Throughput design (same shape as the reference's C++ iterator): the
    whole .rec is mapped into memory and indexed in one native scan
    (`src/io_native.cc mxtpu_recordio_index`); `preprocess_threads`
    workers each build complete batches — cv2 JPEG decode and the native
    crop/mirror/normalize/HWC->CHW kernel both release the GIL, so the
    pool scales — and a reorder buffer hands batches out in order.
    """

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 shuffle=False, rand_crop=False, rand_mirror=False,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0, std_r=1.0, std_g=1.0,
                 std_b=1.0, resize=0, part_index=None, num_parts=None,
                 preprocess_threads=None, prefetch_buffer=4,
                 round_batch=True, data_name="data",
                 label_name="softmax_label", seed=0, fast_decode=True,
                 device_augment=False, **kwargs):
        super().__init__(batch_size)
        if preprocess_threads is None:
            from . import config as _config
            preprocess_threads = _config.get("MXNET_CPU_WORKER_NTHREADS")
        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        self._shuffle = shuffle
        self._rand_crop = rand_crop
        self._rand_mirror = rand_mirror
        self._resize = resize
        self._mean = np.array([mean_r, mean_g, mean_b], dtype="float32")
        # keep the ORIGINAL std too: normalize_symbol passes it to the
        # in-graph ImageNormalize, whose f32 reciprocal then matches the
        # host kernel's `_stdinv` bit-for-bit (uint8-wire parity)
        self._std = np.array([std_r, std_g, std_b], dtype="float32")
        self._stdinv = 1.0 / np.array([std_r, std_g, std_b], dtype="float32")
        # clamp to physical cores: batch builders are CPU-bound (decode +
        # augment), so threads beyond the core count only add GIL ping-pong
        # and working-set thrash (measured −47% at 16 threads on a 1-core
        # host).  The reference's C++ pool is bounded the same way in
        # practice by its decode thread count.
        self._threads = max(1, min(int(preprocess_threads),
                                   os.cpu_count() or 1))
        self._prefetch = max(2, int(prefetch_buffer))
        self._data_name = data_name
        self._label_name = label_name
        self._seed = seed
        self._rng = np.random.RandomState(seed)
        self._epoch = 0
        self._round_batch = round_batch
        # fast_decode: decode JPEGs at 1/2 (or 1/4) resolution straight in
        # libjpeg when the source is comfortably larger than every consumer
        # (resize target / crop window) — the fused decode+downscale trick
        # the reference leaves to full decode + cv::resize.  Falls back to
        # a full decode per image when the reduced frame comes up short.
        self._fast_decode = bool(fast_decode)
        self._fd_tries = 0
        self._fd_wins = 0
        # device_augment: the host stops at crop+mirror and ships uint8
        # NHWC (4x fewer bytes than the fp32 finish, and no float/layout
        # passes on a busy CPU); normalize/cast/NCHW become graph ops —
        # compose the model with `self.normalize_symbol(data)` (the
        # ImageNormalize op), which XLA fuses into the first conv.
        # 'auto'/None-as-string resolves from MXNET_IO_UINT8_WIRE — the
        # production data-plane default; an explicit True/False always
        # wins.
        if isinstance(device_augment, str) and \
                device_augment.lower() in ("auto", "none"):
            from . import config as _config
            device_augment = bool(_config.get("MXNET_IO_UINT8_WIRE"))
        self._device_augment = bool(device_augment)

        import mmap
        self._path_imgrec = path_imgrec
        self._file = open(path_imgrec, "rb")
        self._buf = mmap.mmap(self._file.fileno(), 0,
                              access=mmap.ACCESS_READ)
        self._records, n_corrupt = _index_records_tolerant(self._buf)
        # structural damage found at index time (torn tail, bad magic)
        # plus per-sample decode failures found by the batch builders
        self.corrupt_records = n_corrupt
        self._corrupt_lock = _alocks.make_lock("image.corrupt")
        self._quarantine = None
        # corrupt records the prefetching builders found before a log was
        # attached (`reset()` below starts them): written by set_quarantine
        self._unlogged = []
        if n_corrupt:
            import logging
            logging.getLogger(__name__).warning(
                "ImageRecordIter: %s holds %d corrupt region(s); the "
                "damaged records are skipped (corrupt_records counts "
                "them)", path_imgrec, n_corrupt)
        # per-host input sharding: record ids stay GLOBAL (indexes into
        # the full record list) so quarantine entries keep attributing
        # after a re-shard; the shard only restricts the epoch ORDER.
        # num_parts=None/0/'auto' auto-resolves from this process's
        # (rank, world) — re-resolved at every reset(), so the
        # supervisor's shrink-and-resume re-shards on the epoch fence.
        self._part_index_req = part_index
        self._num_parts_req = num_parts
        self._quarantined = set()
        self.part_index = 0
        self.num_parts = 1
        self._pool = None
        self.reset()

    def _resolve_parts(self):
        """(part_index, num_parts) for the NEXT epoch.  Only an
        EXPLICIT ``num_parts='auto'`` consults the dist environment
        (`io_plane.auto_shard`) — an unset num_parts must stay
        unsharded, or every validation/eval iterator in a dist run
        would silently score 1/N of its data.  MXNET_IO_AUTO_SHARD=0
        is the ops off-switch forcing even 'auto' to a single part."""
        pi, nparts = self._part_index_req, self._num_parts_req
        if nparts == "auto":
            from . import config as _config
            if _config.get("MXNET_IO_AUTO_SHARD"):
                from . import io_plane as _io_plane
                return _io_plane.auto_shard(pi if pi != "auto" else None,
                                            None)
            return 0, 1
        if nparts in (None, 0):
            return 0, 1
        return int(pi or 0), int(nparts)

    def _reshard(self):
        """Recompute this epoch's record order from the resolved shard
        (`recordio.shard_range`: disjoint, exhaustive, deterministic),
        minus quarantined ids."""
        self.part_index, self.num_parts = self._resolve_parts()
        lo, hi = _recordio.shard_range(len(self._records),
                                       self.num_parts, self.part_index)
        if self._quarantined:
            self._order = np.asarray(
                [i for i in range(lo, hi) if i not in self._quarantined],
                dtype=np.int64)
        else:
            self._order = np.arange(lo, hi, dtype=np.int64)

    @property
    def provide_data(self):
        if self._device_augment:
            c, h, w = self.data_shape
            return [DataDesc(self._data_name, (self.batch_size, h, w, c),
                             dtype=np.uint8)]
        return [DataDesc(self._data_name,
                         (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self.label_width == 1 else \
            (self.batch_size, self.label_width)
        return [DataDesc(self._label_name, shape)]

    def normalize_symbol(self, data, dtype="float32"):
        """The graph-side half of device_augment mode: wrap the model's
        input variable so normalize/cast/NCHW run IN the compiled program
        with this iterator's mean/std."""
        from . import symbol as _sym
        mean = tuple(float(v) for v in self._mean)
        # the ORIGINAL std values, not a 1/(1/std) float roundtrip: the
        # op's own f32 reciprocal then equals the host kernel's _stdinv
        # bit-for-bit, so uint8-wire + in-graph normalize reproduces the
        # host-side fp32 path EXACTLY
        std = tuple(float(v) for v in self._std)
        return _sym.ImageNormalize(
            data, mean=mean, std=std, input_layout="NHWC",
            output_layout="NCHW", dtype=dtype)

    def _rebuild_pool(self):
        """(Re)build the batch pool over the current epoch order.
        Reference round_batch semantics: the tail partial batch wraps
        around to the epoch start and reports the wrapped count as
        pad."""
        if self._pool is not None:
            self._pool.stop()
        n = len(self._order)
        n_batches = (-(-n // self.batch_size) if self._round_batch and
                     n % self.batch_size else n // self.batch_size)
        self._pool = _BatchPool(self._build_batch, n_batches, self._threads,
                                self._prefetch)

    def reset(self):
        # the epoch fence: the shard re-resolves here, so a pod that
        # shrank (DMLC_NUM_WORKER rewritten by shrink-and-resume) walks
        # the re-split record set from the next epoch on
        # (_rebuild_pool below stops the previous pool)
        self._reshard()
        if self._shuffle:
            self._rng.shuffle(self._order)
        self._epoch += 1
        self._rebuild_pool()

    def set_quarantine(self, log):
        """Attach a quarantine log: corrupt records the batch builders
        skip append one entry each (source path + record id), those they
        found before the log was attached included."""
        with self._corrupt_lock:
            self._quarantine = log
            found, self._unlogged = self._unlogged, []
        for entry in found:
            log.append(**entry)

    def apply_quarantine(self, entries):
        """Drop previously quarantined record ids for this .rec file
        from the epoch order (resume path: a poisoned record is read
        exactly zero times after diagnosis).  `self._records` is left
        INTACT — record ids must stay stable so entries this run logs
        later still attribute correctly on the next resume; only the
        epoch order loses the poisoned ids."""
        bad = {int(e["record"]) for e in entries
               if e.get("record") is not None and
               e.get("source") in (None, self._path_imgrec)}
        if bad:
            # poisoned ids are remembered on the QUARANTINE SET (not by
            # editing one epoch's order): every future _reshard()
            # excludes them, so a re-shard on the epoch fence cannot
            # resurrect a diagnosed record — and a quarantined record on
            # ANOTHER host's shard simply never intersects this order
            # (the poison stays local to the shard that read it)
            self._quarantined.update(bad)
            self._order = np.asarray(
                [i for i in self._order if int(i) not in bad],
                dtype=np.int64)
            # rebuild the batch pool for the shorter order without
            # advancing the epoch counter (reset() increments it, and
            # the augmentation RNG streams key on the epoch)
            self._rebuild_pool()

    def record_range(self, nbatch):
        """(source, lo, hi) record-position range batch `nbatch` of this
        epoch draws from — the guardian's shard attribution for
        quarantine entries and TrainingDivergedError."""
        lo = int(nbatch) * self.batch_size
        return (self._path_imgrec, lo,
                min(lo + self.batch_size, len(self._order)))

    def _corrupt_record(self, rec_id, exc):
        entry = dict(reason="corrupt_record", source=self._path_imgrec,
                     record=int(rec_id), detail=str(exc)[:200])
        with self._corrupt_lock:
            self.corrupt_records += 1
            n = self.corrupt_records
            log = self._quarantine
            if log is None:
                self._unlogged.append(entry)
        import logging
        logging.getLogger(__name__).warning(
            "ImageRecordIter: record %d of %s is corrupt (%s) — "
            "substituting zeros and quarantining (corrupt_records=%d)",
            rec_id, self._path_imgrec, str(exc)[:120], n)
        if log is not None:
            try:
                log.append(**entry)
            except Exception:
                pass
        try:
            from .resilience import faults as _faults
            _faults.note("corrupt-record", site="io.corrupt_record",
                         record=int(rec_id))
        except Exception:
            pass

    def close(self):
        if self._pool is not None:
            self._pool.stop()
            self._pool = None

    def __del__(self):
        try:
            self.close()
            self._buf.close()
            self._file.close()
        except Exception:
            pass

    def _decode(self, payload, cv2, need):
        """JPEG decode, at reduced libjpeg scale when the frame stays large
        enough for every consumer (`need` = min acceptable shorter side).

        Adaptive: a failed reduced attempt costs a second (full) decode, so
        after a sampling window the reduced path stays on only if most
        images in this corpus are big enough for it."""
        raw = np.frombuffer(payload, np.uint8)
        # only when a resize step follows: the resize renormalizes scale, so
        # decoding at 1/2 changes nothing but cost.  Without resize, a
        # reduced decode would silently double the crop's field of view.
        if self._fast_decode and self._resize > 0 and need > 0 and \
                (self._fd_tries < 16 or self._fd_wins * 2 >= self._fd_tries):
            self._fd_tries += 1
            img = cv2.imdecode(raw, cv2.IMREAD_REDUCED_COLOR_2)
            if img is not None and min(img.shape[:2]) >= need:
                self._fd_wins += 1
                return img
        return cv2.imdecode(raw, cv2.IMREAD_COLOR)

    def _build_batch(self, bidx):
        import cv2
        c, h, w = self.data_shape
        bs = self.batch_size
        label = np.zeros((bs, self.label_width), dtype="float32")
        nat = _native.lib()
        base = bidx * bs
        n_rec = len(self._order)
        pad = max(0, base + bs - n_rec)
        # a per-batch stream keeps augmentation reproducible under any
        # thread schedule: (seed, epoch, batch) fully determines the draws
        rng = np.random.RandomState(
            (self._seed * 1000003 + self._epoch * 8191 + bidx) % (2**31))
        # one vectorized draw per batch (not one python call per record)
        crop_u = rng.rand(bs, 2) if self._rand_crop else None
        mirrors = (rng.rand(bs) < 0.5).astype(np.int32) \
            if self._rand_mirror else np.zeros(bs, np.int32)
        need = self._resize if self._resize else max(h, w)

        imgs = []
        # row-major per-field layout: each row is contiguous for ctypes
        dims = np.empty((4, bs), np.int64)  # rows: ih, iw, y0, x0
        from .resilience import faults as _faults
        for i in range(bs):
            rec_id = int(self._order[(base + i) % n_rec])
            header = img = None
            try:
                raw = _record_payload(self._buf, self._records[rec_id])
                # the payload fault site: a `corrupt` clause bit-flips
                # this record's bytes deterministically
                raw = _faults.mutate("io.corrupt_record", bytes(raw),
                                     record=rec_id)
                header, payload = _recordio.unpack(raw)
                img = self._decode(payload, cv2, need)
                if img is None:
                    raise MXNetError("not a decodable image")
            except Exception as e:
                # a corrupt record must not kill the epoch: substitute a
                # zero image (deterministic), count, and quarantine —
                # the resumed run drops the record entirely
                self._corrupt_record(rec_id, e)
                header, img = None, np.zeros((h, w, c), np.uint8)
            if self._resize:
                ih, iw = img.shape[:2]
                if ih > iw:
                    img = cv2.resize(img, (self._resize,
                                           int(ih * self._resize / iw)))
                else:
                    img = cv2.resize(img, (int(iw * self._resize / ih),
                                           self._resize))
            ih, iw = img.shape[:2]
            if ih < h or iw < w:
                img = cv2.resize(img, (max(iw, w), max(ih, h)))
                ih, iw = img.shape[:2]
            if self._rand_crop:
                y0 = int(crop_u[i, 0] * (ih - h + 1))
                x0 = int(crop_u[i, 1] * (iw - w + 1))
            else:
                y0, x0 = (ih - h) // 2, (iw - w) // 2
            if not img.flags["C_CONTIGUOUS"]:
                img = np.ascontiguousarray(img)
            imgs.append(img)
            dims[:, i] = (ih, iw, y0, x0)
            if header is not None:
                lab = np.asarray(header.label, dtype="float32").reshape(-1)
                label[i, :min(len(lab), self.label_width)] = \
                    lab[:self.label_width]

        # fresh buffer each batch: handed to jax ZERO-COPY below (cpu) or
        # consumed by an async transfer (accelerator) — never recycled, so
        # no defensive copy is needed anywhere on the path
        u8 = self._device_augment
        native_ok = nat is not None
        if native_ok:
            # shared ctypes marshalling for both native finishes
            dims = np.ascontiguousarray(dims)
            ptrs = (ctypes.c_void_p * bs)(
                *(img.ctypes.data for img in imgs))
            i64p = ctypes.POINTER(ctypes.c_int64)
            mirrors_p = np.ascontiguousarray(mirrors).ctypes.data_as(
                ctypes.POINTER(ctypes.c_int))
        if u8:
            # host stops at crop+mirror: uint8 NHWC out (the normalize/
            # cast/layout finish runs in the training program, see
            # normalize_symbol) — no float pass, quarter the bytes
            data = np.empty((bs, h, w, c), dtype=np.uint8)
            if native_ok:
                nat.mxtpu_crop_batch_u8(
                    ptrs, dims[0].ctypes.data_as(i64p),
                    dims[1].ctypes.data_as(i64p), c,
                    dims[2].ctypes.data_as(i64p),
                    dims[3].ctypes.data_as(i64p), h, w, mirrors_p,
                    data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                    bs, 1)
            else:
                for i, img in enumerate(imgs):
                    ih, iw, y0, x0 = dims[:, i]
                    crop = img[y0:y0 + h, x0:x0 + w, ::-1]  # BGR -> RGB
                    if mirrors[i]:
                        crop = crop[:, ::-1]
                    data[i] = crop
            return self._emit(data, label, pad)
        data = np.empty((bs, c, h, w), dtype="float32")
        if native_ok:
            # decoded frames are BGR; the kernel reverses channels on the
            # fly into RGB planes (no cvtColor pass)
            f32p = ctypes.POINTER(ctypes.c_float)
            nat.mxtpu_augment_batch(
                ptrs, dims[0].ctypes.data_as(i64p),
                dims[1].ctypes.data_as(i64p), c,
                dims[2].ctypes.data_as(i64p),
                dims[3].ctypes.data_as(i64p), h, w, mirrors_p,
                self._mean.ctypes.data_as(f32p),
                self._stdinv.ctypes.data_as(f32p),
                data.ctypes.data_as(f32p), bs, 1)
        else:
            for i, img in enumerate(imgs):
                ih, iw, y0, x0 = dims[:, i]
                crop = img[y0:y0 + h, x0:x0 + w, ::-1]  # BGR -> RGB
                if mirrors[i]:
                    crop = crop[:, ::-1]
                data[i] = ((crop.astype("float32") - self._mean)
                           * self._stdinv).transpose(2, 0, 1)
        return self._emit(data, label, pad)

    def _emit(self, data, label, pad):
        label_out = label[:, 0] if self.label_width == 1 else label

        from .context import current_context
        ctx = current_context()
        if ctx.device_type == "cpu":
            # keep the batch as host numpy behind the NDArray: the
            # training step's input staging sends it STRAIGHT to its
            # target device/sharding in one transfer, and eager consumers
            # promote host-backed arrays on first use (invoke()); wrapping
            # in a cpu-backend jax array here would add a slow
            # cross-backend hop on the training hot path
            batch_nd = NDArray(data, ctx=ctx)
            label_nd = NDArray(label_out, ctx=ctx)
        else:
            import jax
            batch_nd = NDArray(jax.device_put(data, ctx.jax_device), ctx=ctx)
            label_nd = array(label_out, ctx=ctx)
        return DataBatch(data=[batch_nd], label=[label_nd],
                         pad=pad, provide_data=self.provide_data,
                         provide_label=self.provide_label)

    def next(self):
        batch = self._pool.next()
        if batch is None:
            raise StopIteration
        return batch


class _WorkerError:
    """A worker exception in transit to the consumer thread."""

    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


class _BatchPool:
    """N workers building whole batches; results handed out in order."""

    def __init__(self, build, n_batches, n_threads, prefetch):
        self._build = build
        self._n = n_batches
        self._stop_evt = threading.Event()
        self._results = {}
        self._cond = _alocks.make_condition(name="image.batchpool")
        self._next_out = 0
        self._max_ahead = max(prefetch, n_threads + 1)
        self._task = iter(range(n_batches))
        self._task_lock = _alocks.make_lock("image.batchpool.tasks")
        self._threads = [threading.Thread(target=self._work, daemon=True,
                                          name=f"mx-io-decode-{i}")
                         for i in range(n_threads)]
        for t in self._threads:
            t.start()

    def _work(self):
        from .obs import metrics as _metrics, trace as _trace
        while not self._stop_evt.is_set():
            with self._task_lock:
                bidx = next(self._task, None)
            if bidx is None:
                return
            with self._cond:
                # bounded read-ahead keeps memory flat
                self._cond.wait_for(
                    lambda: self._stop_evt.is_set()
                    or bidx < self._next_out + self._max_ahead)
                if self._stop_evt.is_set():
                    return
            try:
                with _trace.span("io.decode", cat="io", batch=bidx):
                    out = self._build(bidx)
            except BaseException as e:   # deliver to the consumer, always
                out = _WorkerError(e)
            with self._cond:
                self._results[bidx] = out
                _metrics.registry().gauge("io.decode.queue_depth").set(
                    len(self._results))
                self._cond.notify_all()

    def next(self):
        if self._next_out >= self._n:
            return None
        with self._cond:
            self._cond.wait_for(lambda: self._next_out in self._results)
            out = self._results.pop(self._next_out)
            self._next_out += 1
            self._cond.notify_all()
        if isinstance(out, _WorkerError):
            self.stop()
            raise out.exc
        return out

    def stop(self):
        self._stop_evt.set()
        with self._cond:
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=5)


def _group_parts(parts):
    """Group (offset, length, cflag) physical parts into logical records:
    cflag 0 stands alone; 1/2*/3 sequences form one multi-part record
    (dmlc writers split payloads containing the magic word; see
    `recordio.MXRecordIO.read`).  Structural violations — a truncated
    multi-part sequence, a continuation without a start — drop the
    damaged record and count it instead of raising: a torn tail must not
    make the whole .rec unreadable.  Returns (records, n_corrupt)."""
    records = []
    pending = None
    corrupt = 0
    for off, ln, cf in parts:
        if cf == 0:
            if pending is not None:
                corrupt += 1     # interrupted multi-part: drop it
                pending = None
            records.append([(off, ln)])
        elif cf == 1:
            if pending is not None:
                corrupt += 1
            pending = [(off, ln)]
        elif cf in (2, 3):
            if pending is None:
                corrupt += 1     # continuation without a start
                continue
            pending.append((off, ln))
            if cf == 3:
                records.append(pending)
                pending = None
        else:
            corrupt += 1
            pending = None
    if pending is not None:
        corrupt += 1             # truncated multi-part record at EOF
    return records, corrupt


_REC_MAGIC = __import__("struct").pack("<I", 0xced7230a)


def _record_payload(buf, segments):
    """Payload bytes of one logical record: single-part records slice
    straight from the mapped file; multi-part records are re-joined with
    the magic word the writer dropped at each split."""
    if len(segments) == 1:
        off, ln = segments[0]
        return buf[off:off + ln]
    return _REC_MAGIC.join(bytes(buf[off:off + ln]) for off, ln in segments)


def _index_records_tolerant(buf):
    """Segment lists of every logical record payload — native scan when
    the library is built, struct-walk fallback otherwise.  Each entry is
    a list of (offset, length) parts; pass to `_record_payload`.

    Tolerant of damage: a magic mismatch resynchronizes on the next
    magic word (the bytes in between are one counted corrupt region), a
    truncated tail record stops the scan, and broken multi-part
    sequences are dropped — see `_group_parts`.  A native scan that
    reports invalid structure (-1) falls back to the tolerant walk
    instead of raising.  Returns (records, n_corrupt)."""
    nat = _native.lib()
    parts = None
    corrupt = 0
    if nat is not None:
        cap = max(1024, len(buf) // 12)
        offs = np.empty(cap, dtype=np.int64)
        lens = np.empty(cap, dtype=np.int64)
        cfls = np.empty(cap, dtype=np.int32)
        # zero-copy view works for bytes and (read-only) mmap alike
        view = np.frombuffer(buf, dtype=np.uint8)
        n = nat.mxtpu_recordio_index(
            view.ctypes.data_as(ctypes.c_void_p), len(buf),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            cfls.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), cap)
        if n >= 0:
            parts = list(zip(offs[:n].tolist(), lens[:n].tolist(),
                             cfls[:n].tolist()))
            # the native scan stops silently at a truncated tail; any
            # unconsumed bytes past the last indexed part are one
            # corrupt region (a torn header/payload a writer left)
            end = 0
            if parts:
                off, ln, _ = parts[-1]
                end = off + ln + (4 - ln % 4) % 4
            if len(buf) - end > 0:
                corrupt += 1
        # n == -1: the native scan found invalid structure — take the
        # tolerant python walk below instead of refusing the file
    if parts is None:
        import struct as _struct
        magic_bytes = _struct.pack("<I", 0xced7230a)
        out = []
        pos = 0
        while pos + 8 <= len(buf):
            magic, lrec = _struct.unpack_from("<II", buf, pos)
            if magic != 0xced7230a:
                # resynchronize on the next magic word; the skipped
                # bytes are one corrupt region
                corrupt += 1
                hit = buf.find(magic_bytes, pos + 1)
                if hit == -1:
                    break
                pos = hit
                continue
            length = lrec & ((1 << 29) - 1)
            if pos + 8 + length > len(buf):
                corrupt += 1     # truncated tail record
                break
            out.append((pos + 8, length, lrec >> 29))
            pos += 8 + length + (4 - length % 4) % 4
        parts = out
    records, n_bad = _group_parts(parts)
    return records, corrupt + n_bad


def _index_records(buf):
    """Back-compat face of `_index_records_tolerant`: records only."""
    return _index_records_tolerant(buf)[0]


# detection pipeline shares this namespace in the reference (mx.image.*)
from .image_detection import (DetAugmenter, DetBorrowAug,   # noqa: E402
                              DetRandomSelectAug, DetHorizontalFlipAug,
                              DetRandomCropAug, DetRandomPadAug,
                              CreateDetAugmenter, ImageDetIter)
