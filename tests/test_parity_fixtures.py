"""Parity fixtures for the network-gated components (VERDICT r2 next #5).

No network egress exists here, so REAL pretrained weights cannot be
fetched — but architecture parity can still be proven:

- FID: a torch-side mirror of the pytorch-fid InceptionV3 feature
  extractor (torchvision module naming, the FID-variant pooling) is
  built IN THE TEST with random weights, a real torch forward runs, the
  state dict goes through `convert_torch_state_dict`, and the Flax
  features must match the torch features. This upgrades the converter's
  previous synthetic-roundtrip coverage to cross-framework forward
  parity: any divergence in layout mapping, padding, BN epsilon, or
  pooling shows up as a feature mismatch.
- CLIP: a tiny random config-built FlaxCLIPModel (no download) is
  registered into the metric cache; the clip/clip_score metrics run end
  to end through the REAL model forward (only tokenization is stubbed —
  tokenizers genuinely require vocab files).

SD-VAE (#30) remains gated: diffusers is not installed in this image,
so its parity fixture must be generated where it is (the wrapper's
import gating is covered in test_autoencoder.py).
"""
import numpy as np
import pytest

torch = pytest.importorskip("torch")
import torch.nn.functional as F  # noqa: E402
from torch import nn  # noqa: E402

# ---------------------------------------------------------------------------
# Torch mirror of pytorch-fid's InceptionV3 pool3 feature path
# (torchvision `Inception3` attribute naming => state-dict names the
# converter documents: "Mixed_5b.branch1x1.conv.weight" etc.)
# ---------------------------------------------------------------------------


class TBasicConv(nn.Module):
    def __init__(self, cin, cout, **kw):
        super().__init__()
        self.conv = nn.Conv2d(cin, cout, bias=False, **kw)
        self.bn = nn.BatchNorm2d(cout, eps=0.001)

    def forward(self, x):
        return F.relu(self.bn(self.conv(x)))


def _avgpool(x):
    # pytorch-fid patches torchvision to count_include_pad=False
    return F.avg_pool2d(x, 3, stride=1, padding=1, count_include_pad=False)


class TInceptionA(nn.Module):
    def __init__(self, cin, pool_features):
        super().__init__()
        self.branch1x1 = TBasicConv(cin, 64, kernel_size=1)
        self.branch5x5_1 = TBasicConv(cin, 48, kernel_size=1)
        self.branch5x5_2 = TBasicConv(48, 64, kernel_size=5, padding=2)
        self.branch3x3dbl_1 = TBasicConv(cin, 64, kernel_size=1)
        self.branch3x3dbl_2 = TBasicConv(64, 96, kernel_size=3, padding=1)
        self.branch3x3dbl_3 = TBasicConv(96, 96, kernel_size=3, padding=1)
        self.branch_pool = TBasicConv(cin, pool_features, kernel_size=1)

    def forward(self, x):
        return torch.cat([
            self.branch1x1(x),
            self.branch5x5_2(self.branch5x5_1(x)),
            self.branch3x3dbl_3(self.branch3x3dbl_2(self.branch3x3dbl_1(x))),
            self.branch_pool(_avgpool(x)),
        ], 1)


class TInceptionB(nn.Module):
    def __init__(self, cin):
        super().__init__()
        self.branch3x3 = TBasicConv(cin, 384, kernel_size=3, stride=2)
        self.branch3x3dbl_1 = TBasicConv(cin, 64, kernel_size=1)
        self.branch3x3dbl_2 = TBasicConv(64, 96, kernel_size=3, padding=1)
        self.branch3x3dbl_3 = TBasicConv(96, 96, kernel_size=3, stride=2)

    def forward(self, x):
        return torch.cat([
            self.branch3x3(x),
            self.branch3x3dbl_3(self.branch3x3dbl_2(self.branch3x3dbl_1(x))),
            F.max_pool2d(x, 3, stride=2),
        ], 1)


class TInceptionC(nn.Module):
    def __init__(self, cin, c7):
        super().__init__()
        self.branch1x1 = TBasicConv(cin, 192, kernel_size=1)
        self.branch7x7_1 = TBasicConv(cin, c7, kernel_size=1)
        self.branch7x7_2 = TBasicConv(c7, c7, kernel_size=(1, 7),
                                      padding=(0, 3))
        self.branch7x7_3 = TBasicConv(c7, 192, kernel_size=(7, 1),
                                      padding=(3, 0))
        self.branch7x7dbl_1 = TBasicConv(cin, c7, kernel_size=1)
        self.branch7x7dbl_2 = TBasicConv(c7, c7, kernel_size=(7, 1),
                                         padding=(3, 0))
        self.branch7x7dbl_3 = TBasicConv(c7, c7, kernel_size=(1, 7),
                                         padding=(0, 3))
        self.branch7x7dbl_4 = TBasicConv(c7, c7, kernel_size=(7, 1),
                                         padding=(3, 0))
        self.branch7x7dbl_5 = TBasicConv(c7, 192, kernel_size=(1, 7),
                                         padding=(0, 3))
        self.branch_pool = TBasicConv(cin, 192, kernel_size=1)

    def forward(self, x):
        b7 = self.branch7x7_3(self.branch7x7_2(self.branch7x7_1(x)))
        bd = self.branch7x7dbl_1(x)
        for m in (self.branch7x7dbl_2, self.branch7x7dbl_3,
                  self.branch7x7dbl_4, self.branch7x7dbl_5):
            bd = m(bd)
        return torch.cat([self.branch1x1(x), b7, bd,
                          self.branch_pool(_avgpool(x))], 1)


class TInceptionD(nn.Module):
    def __init__(self, cin):
        super().__init__()
        self.branch3x3_1 = TBasicConv(cin, 192, kernel_size=1)
        self.branch3x3_2 = TBasicConv(192, 320, kernel_size=3, stride=2)
        self.branch7x7x3_1 = TBasicConv(cin, 192, kernel_size=1)
        self.branch7x7x3_2 = TBasicConv(192, 192, kernel_size=(1, 7),
                                        padding=(0, 3))
        self.branch7x7x3_3 = TBasicConv(192, 192, kernel_size=(7, 1),
                                        padding=(3, 0))
        self.branch7x7x3_4 = TBasicConv(192, 192, kernel_size=3, stride=2)

    def forward(self, x):
        b7 = self.branch7x7x3_1(x)
        for m in (self.branch7x7x3_2, self.branch7x7x3_3,
                  self.branch7x7x3_4):
            b7 = m(b7)
        return torch.cat([self.branch3x3_2(self.branch3x3_1(x)), b7,
                          F.max_pool2d(x, 3, stride=2)], 1)


class TInceptionE(nn.Module):
    def __init__(self, cin, pool="avg"):
        super().__init__()
        self.pool = pool
        self.branch1x1 = TBasicConv(cin, 320, kernel_size=1)
        self.branch3x3_1 = TBasicConv(cin, 384, kernel_size=1)
        self.branch3x3_2a = TBasicConv(384, 384, kernel_size=(1, 3),
                                       padding=(0, 1))
        self.branch3x3_2b = TBasicConv(384, 384, kernel_size=(3, 1),
                                       padding=(1, 0))
        self.branch3x3dbl_1 = TBasicConv(cin, 448, kernel_size=1)
        self.branch3x3dbl_2 = TBasicConv(448, 384, kernel_size=3, padding=1)
        self.branch3x3dbl_3a = TBasicConv(384, 384, kernel_size=(1, 3),
                                          padding=(0, 1))
        self.branch3x3dbl_3b = TBasicConv(384, 384, kernel_size=(3, 1),
                                          padding=(1, 0))
        self.branch_pool = TBasicConv(cin, 192, kernel_size=1)

    def forward(self, x):
        b3 = self.branch3x3_1(x)
        b3 = torch.cat([self.branch3x3_2a(b3), self.branch3x3_2b(b3)], 1)
        bd = self.branch3x3dbl_2(self.branch3x3dbl_1(x))
        bd = torch.cat([self.branch3x3dbl_3a(bd), self.branch3x3dbl_3b(bd)],
                       1)
        if self.pool == "max":
            # pytorch-fid's last block (FIDInceptionE_2) max-pools
            bp = F.max_pool2d(x, 3, stride=1, padding=1)
        else:
            bp = _avgpool(x)
        return torch.cat([self.branch1x1(x), b3, bd,
                          self.branch_pool(bp)], 1)


class TorchInceptionFeatures(nn.Module):
    """pool3 feature path with torchvision attribute naming."""

    def __init__(self):
        super().__init__()
        self.Conv2d_1a_3x3 = TBasicConv(3, 32, kernel_size=3, stride=2)
        self.Conv2d_2a_3x3 = TBasicConv(32, 32, kernel_size=3)
        self.Conv2d_2b_3x3 = TBasicConv(32, 64, kernel_size=3, padding=1)
        self.Conv2d_3b_1x1 = TBasicConv(64, 80, kernel_size=1)
        self.Conv2d_4a_3x3 = TBasicConv(80, 192, kernel_size=3)
        self.Mixed_5b = TInceptionA(192, 32)
        self.Mixed_5c = TInceptionA(256, 64)
        self.Mixed_5d = TInceptionA(288, 64)
        self.Mixed_6a = TInceptionB(288)
        self.Mixed_6b = TInceptionC(768, 128)
        self.Mixed_6c = TInceptionC(768, 160)
        self.Mixed_6d = TInceptionC(768, 160)
        self.Mixed_6e = TInceptionC(768, 192)
        self.Mixed_7a = TInceptionD(768)
        self.Mixed_7b = TInceptionE(1280, "avg")
        self.Mixed_7c = TInceptionE(2048, "max")

    def forward(self, x):          # x: [N, 3, 299, 299] in [0, 1]
        x = 2.0 * x - 1.0
        x = self.Conv2d_2b_3x3(self.Conv2d_2a_3x3(self.Conv2d_1a_3x3(x)))
        x = F.max_pool2d(x, 3, stride=2)
        x = self.Conv2d_4a_3x3(self.Conv2d_3b_1x1(x))
        x = F.max_pool2d(x, 3, stride=2)
        for name in ("Mixed_5b", "Mixed_5c", "Mixed_5d", "Mixed_6a",
                     "Mixed_6b", "Mixed_6c", "Mixed_6d", "Mixed_6e",
                     "Mixed_7a", "Mixed_7b", "Mixed_7c"):
            x = getattr(self, name)(x)
        return torch.mean(x, dim=(2, 3))    # [N, 2048]


def _randomize(model: nn.Module, seed: int = 0):
    """Non-degenerate random weights AND random BN running stats (the
    converter maps running stats too — identity stats would hide bugs)."""
    g = torch.Generator().manual_seed(seed)
    with torch.no_grad():
        for m in model.modules():
            if isinstance(m, nn.Conv2d):
                m.weight.normal_(0, 0.05, generator=g)
            elif isinstance(m, nn.BatchNorm2d):
                m.weight.uniform_(0.8, 1.2, generator=g)
                m.bias.normal_(0, 0.1, generator=g)
                m.running_mean.normal_(0, 0.1, generator=g)
                m.running_var.uniform_(0.5, 1.5, generator=g)


@pytest.mark.slow
def test_fid_inception_torch_forward_parity(tmp_path):
    """Flax features == torch features through the FULL converted
    network (layout, padding, BN eps, FID pooling variants)."""
    import jax
    import numpy as np

    from flaxdiff_tpu.metrics.inception import (
        InceptionV3Features,
        convert_torch_state_dict,
        load_inception_params,
    )

    tmodel = TorchInceptionFeatures().eval()
    _randomize(tmodel)
    rng = np.random.default_rng(0)
    x = rng.uniform(0, 1, size=(2, 299, 299, 3)).astype(np.float32)

    with torch.no_grad():
        want = tmodel(torch.from_numpy(
            x.transpose(0, 3, 1, 2))).numpy()    # NHWC -> NCHW

    flat = convert_torch_state_dict(
        {k: v.numpy() for k, v in tmodel.state_dict().items()})
    npz = tmp_path / "inception.npz"
    np.savez(npz, **flat)

    fmodel = InceptionV3Features(resize_input=False)
    variables = jax.jit(fmodel.init)(jax.random.PRNGKey(0),
                                     np.zeros((1, 299, 299, 3), np.float32))
    variables = load_inception_params(variables, str(npz))
    got = np.asarray(jax.jit(fmodel.apply)(variables, x))

    assert got.shape == want.shape == (2, 2048)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# CLIP metrics end to end through a tiny random config-built FlaxCLIP
# ---------------------------------------------------------------------------


class _TinyProcessor:
    """Stands in for AutoProcessor: deterministic 'tokenization' +
    image packing at the tiny model's sizes (vocab files are the one
    genuinely network-bound piece)."""

    def __init__(self, image_size=30, seq_len=8, vocab=99):
        self.image_size = image_size
        self.seq_len = seq_len
        self.vocab = vocab

    def __call__(self, text, images, return_tensors="np", padding=True):
        ids = np.zeros((len(text), self.seq_len), np.int32)
        for i, t in enumerate(text):
            for j, ch in enumerate(t.encode()[: self.seq_len]):
                ids[i, j] = ch % self.vocab
        pixel = np.stack([
            np.transpose(
                np.resize(np.asarray(im, np.float32) / 255.0,
                          (self.image_size, self.image_size, 3)),
                (2, 0, 1))
            for im in images])
        return {"input_ids": ids,
                "attention_mask": np.ones_like(ids),
                "pixel_values": pixel}


@pytest.fixture(scope="module")
def tiny_clip():
    import jax
    from transformers import CLIPConfig, FlaxCLIPModel

    cfg = CLIPConfig(
        text_config=dict(vocab_size=99, hidden_size=16,
                         intermediate_size=32, num_hidden_layers=2,
                         num_attention_heads=2,
                         max_position_embeddings=8),
        vision_config=dict(hidden_size=16, intermediate_size=32,
                           num_hidden_layers=2, num_attention_heads=2,
                           image_size=30, patch_size=10),
        projection_dim=12)
    # transformers initialises a Flax model eagerly, every primitive
    # compiled and launched alone: the same `init_weights` under jit
    init = FlaxCLIPModel.init_weights
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(FlaxCLIPModel, "init_weights",
                   lambda self, rng, shape, params=None: jax.jit(
                       lambda key: init(self, key, shape))(rng))
        model = FlaxCLIPModel(cfg, seed=0)
    return model, _TinyProcessor()


def test_clip_metrics_end_to_end_tiny_model(tiny_clip):
    from flaxdiff_tpu.metrics.clip_metrics import (
        get_clip_metric,
        get_clip_score_metric,
        register_clip_model,
    )
    model, proc = tiny_clip
    register_clip_model("tiny-clip", model, proc)

    rng = np.random.default_rng(0)
    samples = rng.uniform(-1, 1, size=(3, 16, 16, 3)).astype(np.float32)
    batch = {"text": ["a red square", "a cat", "noise"]}

    m = get_clip_metric(modelname="tiny-clip")
    v = m.function(samples, batch)
    assert np.isfinite(v) and 0.0 <= v <= 2.0
    assert m.higher_is_better is False

    s = get_clip_score_metric(modelname="tiny-clip")
    w = s.function(samples, batch)
    assert np.isfinite(w) and 0.0 <= w <= 2.5
    assert s.higher_is_better is True

    # determinism: same inputs -> same value (cache returns same model)
    assert m.function(samples, batch) == v


def test_clip_similarity_math_oracle():
    """cosine/clip_score against a NumPy oracle (weight-free math)."""
    from flaxdiff_tpu.metrics.clip_metrics import (clip_score,
                                                   cosine_similarity)
    rng = np.random.default_rng(1)
    a = rng.normal(size=(4, 8)).astype(np.float32)
    b = rng.normal(size=(4, 8)).astype(np.float32)
    want = np.sum(a * b, -1) / (np.linalg.norm(a, axis=-1)
                                * np.linalg.norm(b, axis=-1))
    np.testing.assert_allclose(np.asarray(cosine_similarity(a, b)), want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(clip_score(a, b)),
                               2.5 * np.maximum(want, 0), rtol=1e-5,
                               atol=1e-5)