"""No layout copy between a convolution and a GroupNorm+SiLU at a
training batch: a `ConvLayer -> FusedGroupNormSiLU -> ConvLayer` sandwich
with its gradient, compiled for a described v5e chip (nothing attached,
nothing runs). At batch 32 the shape picks the XLA composition: no
`fdt_gn_silu_*` custom call, and the one activation-sized `copy` left is
the program's own result. At batch 8 the four kernels run, each fed by
a `copy` out of the convolutions' layout: the cost the rule avoids.
Beside it, the routed experts' layer (`ops/moe.py`) at the widths of the
benchmark's three cells that run it: Mosaic accepts its three kernels and
no buffer of the program holds the worst case (the third cell holds every
expert: there the worst case IS the case; its windowed grouped flash call
is compiled with it). The one file of `tests/`
that loads the TPU's compiler: the topology is described inside a
fixture, never at import.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) "
                    r"([\w\-]+)\((.*)")
_PASS_THROUGH = ("bitcast", "get-tuple-element", "reshape")


def _elements(shape: str) -> int:
    m = re.match(r"\w+\[([\d,]*)\]", shape)
    n = 1
    for d in (m.group(1).split(",") if m and m.group(1) else []):
        n *= int(d)
    return n


def entry_instructions(hlo_text: str):
    """{name: (opcode, [operand names], elements)} of the ENTRY
    computation (elements of a tuple-shaped result read 1)."""
    start = hlo_text.index("ENTRY ")
    body = hlo_text[start:hlo_text.index("\n}", start)]
    out = {}
    for line in body.split("\n"):
        m = _INSTR.match(line)
        if m:
            name, shape, op, rest = m.groups()
            out[name] = (op, re.findall(r"%([\w.\-]+)",
                                        rest.split("), ")[0]),
                         _elements(shape))
    return out


def copies_feeding(instrs, prefix: str, min_elements: int):
    """(kernel call, copy) pairs: operands of custom calls named
    `prefix*` that a `copy` of at least `min_elements` produces, seen
    through bitcasts (the per-sample statistics reach the kernels
    through copies of a few KB, which do not count)."""
    found = []
    for name, (op, operands, _) in instrs.items():
        if op != "custom-call" or not name.startswith(prefix):
            continue
        for o in operands:
            while o in instrs and instrs[o][0] in _PASS_THROUGH \
                    and instrs[o][1]:
                o = instrs[o][1][0]
            if o in instrs and instrs[o][0] == "copy" \
                    and instrs[o][2] >= min_elements:
                found.append((name, o))
    return found


def _compiled_sandwich(one_chip, monkeypatch, batch):
    """(ENTRY instructions, names of its `fdt_gn_silu_*` calls, elements
    of one activation) of the sandwich's gradient compiled for a v5e."""
    import flax.linen as nn

    from flaxdiff_tpu.models.common import ConvLayer, FusedGroupNormSiLU
    from flaxdiff_tpu.ops import fused_norm

    # the program asks jax for its first device (here the CPU) before it
    # picks the kernels; the test steers it to the TPU's path
    monkeypatch.setattr(fused_norm, "_use_pallas",
                        lambda interpret, force_pallas: (True, False))

    class Sandwich(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = ConvLayer("conv", 128, dtype=jnp.bfloat16)(x)
            x = FusedGroupNormSiLU(groups=8)(x)
            return ConvLayer("conv", 128, dtype=jnp.bfloat16)(x)

    model = Sandwich()

    def loss(params, x):
        return jnp.sum(model.apply(params, x).astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct((batch, 64, 64, 128), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros(x.shape, x.dtype)))
    # compiled as the chip's programs are, not at the suite's cheap
    # settings (conftest.py `CHEAP_COMPILE_FLAGS`): what is read here is
    # what the TPU's compiler makes of the program
    instrs = entry_instructions(
        jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x)
        .compile(compiler_options={
            "xla_backend_optimization_level": 3,
            "xla_llvm_disable_expensive_passes": False}).as_text())
    kernels = sorted(n.rsplit(".", 1)[0] for n, (op, _, _) in instrs.items()
                     if op == "custom-call" and n.startswith("fdt_gn_silu_"))
    return instrs, kernels, batch * 64 * 64 * 128


def test_training_batch_has_no_kernel_and_no_copy_beside_the_norm(
        one_chip, monkeypatch):
    instrs, kernels, activation = _compiled_sandwich(one_chip, monkeypatch, 32)
    assert kernels == []
    copies = [n for n, (op, _, size) in instrs.items()
              if op == "copy" and size >= activation]
    assert len(copies) <= 1, copies     # dx, in the caller's layout


def test_small_batch_runs_the_kernels_behind_layout_copies(
        one_chip, monkeypatch):
    instrs, kernels, activation = _compiled_sandwich(one_chip, monkeypatch, 8)
    assert kernels == sorted(f"fdt_gn_silu_{k}" for k in
                             ("stats", "apply", "bwd_sums", "bwd_dx"))
    fed = {call for call, _ in
           copies_feeding(instrs, "fdt_gn_silu_", activation)}
    assert len(fed) == 4, fed


# cell: (tokens a call, picks a token, hidden, expert width, experts
# held, the layer's experts)
ROUTED = {"glm-5.2.generate-fewer-1024": (8348, 8, 6144, 2048, 16, 256),
          "command-a-plus.generate-few": (5344, 8, 4096, 4096, 16, 128)}


@pytest.mark.parametrize("cell", list(ROUTED))
def test_the_routed_layer_is_sized_by_the_picks_that_land_here(
        one_chip, monkeypatch, cell):
    from flaxdiff_tpu.ops import moe
    n, k, d, f, held, total = ROUTED[cell]
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)

    def on(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(lambda *a: moe.routed_experts(*a, total)).lower(
        on(n, d), on(n, k, dtype=jnp.int32), on(n, k, dtype=jnp.float32),
        on(held, d, f), on(held, d, f), on(held, f, d)).compile()
    text = compiled.as_text()
    for kernel in ("fdt_moe_gmm_gate_up", "fdt_moe_gmm_down",
                   "fdt_moe_combine"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    # the grouped buffer holds a pass's capacity, a sixth (GLM) or under
    # a third (Command A+) of the worst case, which nothing holds
    rows = moe.buffer_rows(moe.capacity(n * k, held, total), held)
    worst = moe.buffer_rows(n * k, held)
    assert rows * 3 < worst
    assert f"bf16[{rows},{d}]" in text and f"[{worst},{d}]" not in text \
        and f"[{n},{k},{d}]" not in text
    # the accumulator is updated in place: no copy of it a pass
    assert not re.search(rf"= f32\[{n},{d}\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * rows * d * 2 + 2 * n * d * 4


def test_a_layer_whose_experts_are_all_held_and_its_windowed_attention(
        one_chip, monkeypatch):
    """The third routed cell's widths (`smallthinker-21b-dn-1536`): a
    guided row's 18,588 tokens x 6 picks over ALL 64 ReLU-gated experts
    of width 768 (`held == total`: one pass holds every pick), and the
    flash forward at 7 query heads a key/value head over 9,294 tokens
    under the 4,096 window, which binds and is named so."""
    from flaxdiff_tpu.ops import moe
    from flaxdiff_tpu.ops.flash_attention import flash_attention
    n, k, d, f, held = 18588, 6, 2560, 768, 64
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)

    def on(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(lambda *a: moe.routed_experts(*a, held, "relu")).lower(
        on(n, d), on(n, k, dtype=jnp.int32), on(n, k, dtype=jnp.float32),
        on(held, d, f), on(held, d, f), on(held, f, d)).compile().as_text()
    for kernel in ("fdt_moe_gmm_gate_up", "fdt_moe_gmm_down",
                   "fdt_moe_combine"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    assert moe.capacity(n * k, held, held) == n * k
    assert f"bf16[{moe.buffer_rows(n * k, held)},{d}]" in text
    assert f"[{n},{k},{d}]" not in text
    q, kv = on(2, 9294, 28, 128), on(2, 9294, 4, 128)
    for window, name in ((4096, "fdt_flash_fwd_window"),
                         (None, "fdt_flash_fwd")):
        text = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, None, None, None, False, True, window)).lower(
            q, kv, kv).compile().as_text()
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == 1, name
        # seven heads' rows a block, padded to the blocks: 9,344 / 9,728
        assert "bf16[8,7,9344,128]" in text and "bf16[8,9728,128]" in text
