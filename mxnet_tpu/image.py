"""mx.image — image IO, resize/crop helpers, augmenters, ImageIter.

Reference: python/mxnet/image/image.py (imdecode/imresize/crops,
Augmenter classes, CreateAugmenter, ImageIter) over the C++ pipeline
src/io/image_aug_default.cc.

TPU-native notes: per-sample decode/augment stays on host (cv2/PIL +
numpy — these release the GIL inside DataLoader threads); the batched
tensor is transferred to HBM once.  That is exactly the reference's
split (OpenCV on CPU workers → device copy in the executor).
"""

from __future__ import annotations

import os
import threading

import numpy as _np

from . import io as _io
from . import ndarray, recordio
from .base import MXNetError

__all__ = ["imdecode", "imread", "imresize", "resize_short", "fixed_crop",
           "center_crop", "random_crop", "random_size_crop", "color_normalize",
           "Augmenter", "ResizeAug", "ForceResizeAug", "RandomCropAug",
           "CenterCropAug", "HorizontalFlipAug", "CastAug",
           "ColorNormalizeAug", "BrightnessJitterAug", "ContrastJitterAug",
           "SaturationJitterAug", "HueJitterAug", "LightingAug",
           "ColorJitterAug", "RandomOrderAug", "RandomGrayAug",
           "SequentialAug", "CreateAugmenter", "ImageIter"]


def _cv2():
    try:
        import cv2

        return cv2
    except ImportError:
        return None


class _HostArray(_np.ndarray):
    """numpy view that also answers the NDArray read surface augmenters
    use (`asnumpy`), so user augmenters written against the documented
    NDArray contract keep working on the host-numpy fast path."""

    def asnumpy(self):
        return _np.asarray(self)


def _to_host(src):
    """NDArray|numpy -> numpy view on host.  The whole augmentation
    chain runs on host numpy (one HBM transfer per *batch*, not per
    sample/op — a per-op device round trip also costs a fresh XLA
    compile per crop shape)."""
    return src.asnumpy() if isinstance(src, ndarray.NDArray) else src


def _like(out, ref):
    """Wrap a host array to match the caller's container type, so the
    public augmenter API stays NDArray->NDArray (reference behavior)
    while iterators feed host arrays through the same objects."""
    if isinstance(ref, ndarray.NDArray):
        return ndarray.array(out)
    return out.view(_HostArray) if isinstance(out, _np.ndarray) else out


def _imdecode_np(buf, flag=1, to_rgb=True):
    """Decode an image byte buffer to a host HWC uint8 numpy array."""
    if bytes(buf[:4]) == b"IMG0":
        # records written by earlier versions of this framework carried a
        # format tag before the encoded bytes; no real image format
        # starts with IMG0, so stripping it is unambiguous
        buf = buf[4:]
    cv2 = _cv2()
    if cv2 is not None:
        arr = cv2.imdecode(_np.frombuffer(buf, dtype=_np.uint8),
                           cv2.IMREAD_COLOR if flag else cv2.IMREAD_GRAYSCALE)
        if arr is None:
            raise MXNetError("imdecode failed")
        if flag and to_rgb:
            arr = arr[:, :, ::-1]
        if not flag:
            arr = arr[:, :, None]
    else:
        import io as _pyio

        from PIL import Image

        img = Image.open(_pyio.BytesIO(buf))
        img = img.convert("RGB" if flag else "L")
        arr = _np.asarray(img)
        if not flag:
            arr = arr[:, :, None]
        elif not to_rgb:
            arr = arr[:, :, ::-1]
    return _np.ascontiguousarray(arr)


def imdecode(buf, flag=1, to_rgb=True, out=None):
    """Decode an image byte buffer to an NDArray HWC(BGR→RGB)
    (reference: image.py imdecode over cv::imdecode)."""
    return ndarray.array(_imdecode_np(buf, flag=flag, to_rgb=to_rgb),
                         dtype="uint8")


def imread(filename, flag=1, to_rgb=True):
    with open(filename, "rb") as f:
        return imdecode(f.read(), flag=flag, to_rgb=to_rgb)


def imresize(src, w, h, interp=1):
    """Resize HWC image (reference: image.py imresize).  Type-preserving:
    numpy in -> numpy out, NDArray in -> NDArray out."""
    arr = _to_host(src)
    cv2 = _cv2()
    if cv2 is not None:
        out = cv2.resize(arr, (int(w), int(h)),
                         interpolation=_cv2_interp(interp))
        if out.ndim == 2:
            out = out[:, :, None]
    else:
        from .gluon.data.vision.transforms import _resize_np

        out = _resize_np(arr, (int(w), int(h)))
    return _like(out.astype(arr.dtype, copy=False), src)


def _cv2_interp(interp):
    import cv2

    return {0: cv2.INTER_NEAREST, 1: cv2.INTER_LINEAR, 2: cv2.INTER_CUBIC,
            3: cv2.INTER_AREA, 4: cv2.INTER_LANCZOS4}.get(int(interp),
                                                          cv2.INTER_LINEAR)


def resize_short(src, size, interp=2):
    """Resize so the shorter side equals `size`, keeping aspect
    (reference: image.py resize_short)."""
    h, w = src.shape[:2]
    if h > w:
        new_h, new_w = size * h // w, size
    else:
        new_h, new_w = size, size * w // h
    return imresize(src, new_w, new_h, interp)


def fixed_crop(src, x0, y0, w, h, size=None, interp=2):
    # crop on host: NDArray slicing would trace one XLA program per
    # distinct crop shape
    out = _like(_to_host(src)[y0:y0 + h, x0:x0 + w], src)
    if size is not None and (w, h) != size:
        out = imresize(out, size[0], size[1], interp)
    return out


def center_crop(src, size, interp=2):
    h, w = src.shape[:2]
    new_w, new_h = size
    x0 = (w - new_w) // 2
    y0 = (h - new_h) // 2
    out = fixed_crop(src, x0, y0, new_w, new_h)
    return out, (x0, y0, new_w, new_h)


class _SampleScopedStream:
    """RNG facade for augmenter draws over any random-module-like
    fallback (np.random here; the Python `random` module for the det
    augmenters in image_detection.py).

    By default every attribute resolves to the fallback's global
    stream, so single-threaded augmentation reproduces under
    np.random.seed/random.seed exactly as before.  A preprocess worker
    thread installs a per-sample generator (seeded by a draw the
    CALLING thread made from the global stream), so
    preprocess_threads>1 keeps sample contents reproducible no matter
    which pool thread runs which sample — the property the reference
    gets from per-worker seeded RNGs
    (src/io/iter_image_recordio_2.cc kRandMagic).  ADVICE r4 #3.
    """

    def __init__(self, fallback):
        self._fallback = fallback
        self._local = threading.local()

    def set_sample_rng(self, rng):
        self._local.rng = rng

    def __getattr__(self, name):
        rng = getattr(self._local, "rng", None)
        return getattr(self._fallback if rng is None else rng, name)


_nprand = _SampleScopedStream(_np.random)


def random_crop(src, size, interp=2):
    h, w = src.shape[:2]
    new_w, new_h = min(size[0], w), min(size[1], h)
    x0 = _nprand.randint(0, w - new_w + 1)
    y0 = _nprand.randint(0, h - new_h + 1)
    out = fixed_crop(src, x0, y0, new_w, new_h, size, interp)
    return out, (x0, y0, new_w, new_h)


def random_size_crop(src, size, area, ratio, interp=2):
    h, w = src.shape[:2]
    src_area = h * w
    if isinstance(area, (int, float)):
        area = (area, 1.0)
    for _ in range(10):
        target_area = _nprand.uniform(*area) * src_area
        log_ratio = (_np.log(ratio[0]), _np.log(ratio[1]))
        aspect = _np.exp(_nprand.uniform(*log_ratio))
        new_w = int(round(_np.sqrt(target_area * aspect)))
        new_h = int(round(_np.sqrt(target_area / aspect)))
        if new_w <= w and new_h <= h:
            x0 = _nprand.randint(0, w - new_w + 1)
            y0 = _nprand.randint(0, h - new_h + 1)
            out = fixed_crop(src, x0, y0, new_w, new_h, size, interp)
            return out, (x0, y0, new_w, new_h)
    return center_crop(src, size, interp)


def color_normalize(src, mean, std=None):
    """(src - mean) / std; either stat may be None (reference:
    image.py color_normalize tolerates std-only / mean-only)."""
    arr = _to_host(src).astype(_np.float32)
    if mean is not None:
        arr = arr - _np.asarray(mean, dtype=_np.float32)
    if std is not None:
        arr = arr / _np.asarray(std, dtype=_np.float32)
    return _like(arr, src)


# ------------------------------------------------------------- augmenters


class Augmenter:
    """Image augmenter base (reference: image.py Augmenter)."""

    def __init__(self, **kwargs):
        # array-valued kwargs (mean/std) become lists so dumps() emits
        # plain json (reference: image.py Augmenter.__init__)
        self._kwargs = {
            k: (v.asnumpy().tolist() if isinstance(v, ndarray.NDArray)
                else v.tolist() if isinstance(v, _np.ndarray) else v)
            for k, v in kwargs.items()}

    def dumps(self):
        import json

        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, src):
        raise NotImplementedError


class ResizeAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size
        self.interp = interp

    def __call__(self, src):
        return resize_short(src, self.size, self.interp)


class ForceResizeAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size
        self.interp = interp

    def __call__(self, src):
        return imresize(src, self.size[0], self.size[1], self.interp)


class RandomCropAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size
        self.interp = interp

    def __call__(self, src):
        return random_crop(src, self.size, self.interp)[0]


class CenterCropAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size
        self.interp = interp

    def __call__(self, src):
        return center_crop(src, self.size, self.interp)[0]


class HorizontalFlipAug(Augmenter):
    def __init__(self, p):
        super().__init__(p=p)
        self.p = p

    def __call__(self, src):
        if _nprand.rand() < self.p:
            return _like(_to_host(src)[:, ::-1].copy(), src)
        return src


class CastAug(Augmenter):
    def __init__(self, typ="float32"):
        super().__init__(type=typ)
        self.typ = typ

    def __call__(self, src):
        if isinstance(src, ndarray.NDArray):
            return src.astype(self.typ)
        return src.astype(self.typ, copy=False)


class ColorNormalizeAug(Augmenter):
    def __init__(self, mean, std):
        super().__init__(mean=mean, std=std)
        self.mean = mean
        self.std = std

    def __call__(self, src):
        return color_normalize(src, self.mean, self.std)


class BrightnessJitterAug(Augmenter):
    def __init__(self, brightness):
        super().__init__(brightness=brightness)
        self.brightness = brightness

    def __call__(self, src):
        alpha = 1.0 + _nprand.uniform(-self.brightness, self.brightness)
        return _like(_to_host(src).astype(_np.float32) * alpha, src)


class ContrastJitterAug(Augmenter):
    _coef = _np.array([[[0.299, 0.587, 0.114]]], dtype=_np.float32)

    def __init__(self, contrast):
        super().__init__(contrast=contrast)
        self.contrast = contrast

    def __call__(self, src):
        alpha = 1.0 + _nprand.uniform(-self.contrast, self.contrast)
        arr = _to_host(src).astype(_np.float32)
        gray = (arr * self._coef).sum() * (3.0 / arr.size)
        return _like(arr * alpha + gray * (1.0 - alpha), src)


class SaturationJitterAug(Augmenter):
    _coef = _np.array([[[0.299, 0.587, 0.114]]], dtype=_np.float32)

    def __init__(self, saturation):
        super().__init__(saturation=saturation)
        self.saturation = saturation

    def __call__(self, src):
        alpha = 1.0 + _nprand.uniform(-self.saturation, self.saturation)
        arr = _to_host(src).astype(_np.float32)
        gray = (arr * self._coef).sum(axis=2, keepdims=True)
        return _like(arr * alpha + gray * (1.0 - alpha), src)


class HueJitterAug(Augmenter):
    def __init__(self, hue):
        super().__init__(hue=hue)
        self.hue = hue

    def __call__(self, src):
        # yiq rotation (reference: image.py HueJitterAug)
        alpha = _nprand.uniform(-self.hue, self.hue)
        u = _np.cos(alpha * _np.pi)
        w = _np.sin(alpha * _np.pi)
        bt = _np.array([[1.0, 0.0, 0.0], [0.0, u, -w], [0.0, w, u]])
        tyiq = _np.array([[0.299, 0.587, 0.114], [0.596, -0.274, -0.321],
                          [0.211, -0.523, 0.311]])
        ityiq = _np.array([[1.0, 0.956, 0.621], [1.0, -0.272, -0.647],
                           [1.0, -1.107, 1.705]])
        t = _np.dot(_np.dot(ityiq, bt), tyiq).T
        arr = _to_host(src).astype(_np.float32)
        return _like(_np.dot(arr, t).astype(_np.float32), src)


class LightingAug(Augmenter):
    def __init__(self, alphastd, eigval, eigvec):
        super().__init__(alphastd=alphastd)
        self.alphastd = alphastd
        self.eigval = _np.asarray(eigval, dtype=_np.float32)
        self.eigvec = _np.asarray(eigvec, dtype=_np.float32)

    def __call__(self, src):
        alpha = _nprand.normal(0, self.alphastd, size=(3,))
        rgb = _np.dot(self.eigvec * alpha, self.eigval)
        return _like(_to_host(src).astype(_np.float32) + rgb, src)


class ColorJitterAug(Augmenter):
    def __init__(self, brightness, contrast, saturation):
        super().__init__(brightness=brightness, contrast=contrast,
                         saturation=saturation)
        self.augs = []
        if brightness > 0:
            self.augs.append(BrightnessJitterAug(brightness))
        if contrast > 0:
            self.augs.append(ContrastJitterAug(contrast))
        if saturation > 0:
            self.augs.append(SaturationJitterAug(saturation))

    def __call__(self, src):
        for i in _nprand.permutation(len(self.augs)):
            src = self.augs[i](src)
        return src


class RandomGrayAug(Augmenter):
    """Convert to 3-channel grayscale with probability p (reference:
    image.py RandomGrayAug)."""

    _coef = _np.array([[[0.299, 0.587, 0.114]]], dtype=_np.float32)

    def __init__(self, p):
        super().__init__(p=p)
        self.p = p

    def __call__(self, src):
        if _nprand.rand() < self.p:
            arr = _to_host(src).astype(_np.float32)
            gray = (arr * self._coef).sum(axis=2, keepdims=True)
            return _like(_np.broadcast_to(
                gray, gray.shape[:2] + (3,)).copy(), src)
        return src


class RandomOrderAug(Augmenter):
    def __init__(self, ts):
        super().__init__()
        self.ts = ts

    def __call__(self, src):
        for i in _nprand.permutation(len(self.ts)):
            src = self.ts[i](src)
        return src


class SequentialAug(Augmenter):
    def __init__(self, ts):
        super().__init__()
        self.ts = ts

    def __call__(self, src):
        for t in self.ts:
            src = t(src)
        return src


def CreateAugmenter(data_shape, resize=0, rand_crop=False, rand_resize=False,
                    rand_mirror=False, mean=None, std=None, brightness=0,
                    contrast=0, saturation=0, hue=0, pca_noise=0,
                    rand_gray=0, inter_method=2):
    """Build the standard augmenter list (reference: image.py
    CreateAugmenter)."""
    auglist = []
    if resize > 0:
        auglist.append(ResizeAug(resize, inter_method))
    crop_size = (data_shape[2], data_shape[1])
    if rand_resize:
        auglist.append(_RandomSizedCropAug(crop_size, 0.08, (3.0 / 4.0, 4.0 / 3.0),
                                           inter_method))
    elif rand_crop:
        auglist.append(RandomCropAug(crop_size, inter_method))
    else:
        auglist.append(CenterCropAug(crop_size, inter_method))
    if rand_mirror:
        auglist.append(HorizontalFlipAug(0.5))
    auglist.append(CastAug())
    if brightness or contrast or saturation:
        auglist.append(ColorJitterAug(brightness, contrast, saturation))
    if hue:
        auglist.append(HueJitterAug(hue))
    if pca_noise > 0:
        eigval = _np.array([55.46, 4.794, 1.148])
        eigvec = _np.array([[-0.5675, 0.7192, 0.4009],
                            [-0.5808, -0.0045, -0.8140],
                            [-0.5836, -0.6948, 0.4203]])
        auglist.append(LightingAug(pca_noise, eigval, eigvec))
    if mean is True:
        mean = _np.array([123.68, 116.28, 103.53])
    if std is True:
        std = _np.array([58.395, 57.12, 57.375])
    if rand_gray > 0:
        auglist.append(RandomGrayAug(rand_gray))
    if mean is not None and len(_np.atleast_1d(mean)) > 0:
        auglist.append(ColorNormalizeAug(mean, std))
    return auglist


class _RandomSizedCropAug(Augmenter):
    def __init__(self, size, area, ratio, interp=2):
        super().__init__()
        self.size = size
        self.area = area
        self.ratio = ratio
        self.interp = interp

    def __call__(self, src):
        return random_size_crop(src, self.size, (self.area, 1.0), self.ratio,
                                self.interp)[0]


# ------------------------------------------------------------- ImageIter


class ImageIter(_io.DataIter):
    """Image data iterator with augmenters, reading .rec or an imglist
    (reference: image.py ImageIter over ImageRecordIter)."""

    def __init__(self, batch_size, data_shape, label_width=1,
                 path_imgrec=None, path_imglist=None, path_root=None,
                 part_index=0, num_parts=1, aug_list=None, imglist=None,
                 shuffle=False, data_name="data", label_name="softmax_label",
                 **kwargs):
        super().__init__(batch_size)
        assert len(data_shape) == 3
        self.data_shape = tuple(data_shape)
        self.batch_size = batch_size
        self.label_width = label_width
        self.shuffle = shuffle
        self._data_name = data_name
        self._label_name = label_name
        self.auglist = aug_list if aug_list is not None else \
            CreateAugmenter(data_shape, **kwargs)

        self.seq = None
        self.imgrec = None
        self.imglist = None
        self.path_root = path_root
        if path_imgrec:
            idx_path = os.path.splitext(path_imgrec)[0] + ".idx"
            self.imgrec = recordio.MXIndexedRecordIO(idx_path, path_imgrec,
                                                     "r")
            self.seq = list(self.imgrec.keys)
        elif path_imglist:
            with open(path_imglist) as f:
                result = {}
                for line in f:
                    parts = line.strip().split("\t")
                    label = _np.array(parts[1:-1], dtype=_np.float32)
                    result[int(parts[0])] = (label, parts[-1])
            self.imglist = result
            self.seq = list(result.keys())
        elif imglist is not None:
            result = {}
            for i, item in enumerate(imglist):
                result[i] = (_np.asarray(item[0], dtype=_np.float32)
                             if not _np.isscalar(item[0])
                             else _np.array([item[0]], dtype=_np.float32),
                             item[1])
            self.imglist = result
            self.seq = list(result.keys())
        else:
            raise ValueError("must supply path_imgrec, path_imglist or imglist")
        if num_parts > 1:
            self.seq = self.seq[part_index::num_parts]
        self.cur = 0
        self.reset()

    @property
    def provide_data(self):
        return [_io.DataDesc(self._data_name,
                             (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self.label_width == 1 else \
            (self.batch_size, self.label_width)
        return [_io.DataDesc(self._label_name, shape)]

    def reset(self):
        if self.shuffle:
            _np.random.shuffle(self.seq)
        self.cur = 0

    def next_sample(self):
        if self.cur >= len(self.seq):
            raise StopIteration
        idx = self.seq[self.cur]
        self.cur += 1
        if self.imgrec is not None:
            rec = self.imgrec.read_idx(idx)
            header, img = recordio.unpack(rec)
            return header.label, img
        label, fname = self.imglist[idx]
        with open(os.path.join(self.path_root or "", fname), "rb") as f:
            return label, f.read()

    def next(self):
        batch_data = _np.zeros((self.batch_size,) + self.data_shape,
                               dtype=_np.float32)
        batch_label = _np.zeros((self.batch_size, self.label_width),
                                dtype=_np.float32)
        i = 0
        try:
            while i < self.batch_size:
                label, buf = self.next_sample()
                # whole chain on host numpy; _HostArray keeps the
                # NDArray read surface for user-supplied augmenters
                img = _imdecode_np(buf).view(_HostArray)
                for aug in self.auglist:
                    img = aug(img)
                batch_data[i] = _to_host(img).transpose(2, 0, 1)
                batch_label[i] = _np.atleast_1d(label)[:self.label_width]
                i += 1
        except StopIteration:
            if i == 0:
                raise
        if self.label_width == 1:
            batch_label = batch_label[:, 0]
        return _io.DataBatch(
            data=[ndarray.array(batch_data)],
            label=[ndarray.array(batch_label)],
            pad=self.batch_size - i)


# detection-aware augmenters + ImageDetIter live in image_detection.py;
# surfaced here to match the reference's mx.image namespace
from .image_detection import (  # noqa: E402
    CreateDetAugmenter, CreateMultiRandCropAugmenter, DetAugmenter,
    DetBorrowAug, DetHorizontalFlipAug, DetRandomCropAug, DetRandomPadAug,
    DetRandomSelectAug, ImageDetIter)

__all__ += ["DetAugmenter", "DetBorrowAug", "DetRandomSelectAug",
            "DetHorizontalFlipAug", "DetRandomCropAug", "DetRandomPadAug",
            "CreateMultiRandCropAugmenter", "CreateDetAugmenter",
            "ImageDetIter"]
