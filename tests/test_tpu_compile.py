"""Compile-only guards: the kernels of the looped cell's main path at the
published widths, compiled for a described v5e chip (nothing runs; no chip
is needed).  The topology is described inside a fixture, never at import:
only one process may hold the TPU's library, and every xdist worker imports
this file.  Keep such tests in this one file."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from theanompi_tpu.jax_compat import shard_map
from theanompi_tpu.models import layers as L


@pytest.fixture(scope="module")
def topo():
    """Skipped only where the TPU's compiler is not installed at all: where
    it is, on the chip's machine as in the sandbox, a topology that cannot
    be described fails these guards, it does not silence them."""
    import importlib.util

    from jax.experimental import topologies
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no libtpu installed: nothing can compile for a v5e")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip_mesh(topo):
    return Mesh(np.array(topo.devices[:1]), ("workers",))


def test_flash_attention_compiles_inside_the_steps_shard_map(one_chip_mesh):
    """``attn_impl='flash'`` forward and backward at 16 heads of 128 over
    two sequences of 4,096, under ``jax.checkpoint`` as the looped stack
    applies a layer, inside a ``shard_map`` that checks vma as every step
    of this package does: jax's kernel builds its out_shapes without
    ``vma`` and is refused there but for ``jax_compat.splash_attention``."""
    mesh = one_chip_mesh
    attn = L.RotaryAttention(2048, 16, theta=1e6, attn_impl="flash",
                             name="attn")
    params = jax.eval_shape(attn.init, jax.random.key(0))

    def per_worker(p, x):
        def loss(p, x):
            return jnp.sum(jax.checkpoint(attn.apply)(p, x[0]).astype(
                jnp.float32))
        return jax.tree.map(lambda g: g[None], jax.value_and_grad(loss)(
            jax.tree.map(lambda a: a[0], p), x))

    spec = P("workers")
    sh = NamedSharding(mesh, spec)
    boxed = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (1,) + a.shape, a.dtype, sharding=sh), params)
    x = jax.ShapeDtypeStruct((1, 2, 4096, 2048), jnp.float32, sharding=sh)
    step = jax.jit(shard_map(per_worker, mesh=mesh, in_specs=(spec, spec),
                             out_specs=spec))
    text = step.lower(boxed, x).compile().as_text()
    # the forward kernel, the same again for the backward pass, and one
    # fused backward kernel for dq, dk and dv: no kernel of dq's own
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "splash_mha_fwd_residuals" in text
    assert "splash_mha_dkv_no_residuals" in text
    assert "splash_mha_dq" not in text and "flash_mha" not in text
    # at the tiles layers.flash_tiles gives this length, 32 heads in one
    # call (the batch goes in as heads)
    for name, size in L.flash_tiles(4096).items():
        assert f'{name}\\": {json.dumps(size)}' in text, name
    assert "bf16[32,4096,128]" in text
    assert "attn_core" in text                  # the scope reaches the HLO


@pytest.mark.parametrize("kind, heads, window", [
    ("sliding_attention", 18, 512), ("full_attention", 12, None)])
def test_mixed_attention_compiles_at_the_routed_cells_shapes(
        one_chip_mesh, kind, heads, window):
    """The routed cell's two attention layers at a quarter of the
    published heads, 18 or 12 query heads over 2 key/value heads of 128,
    one sequence of 8,192, hidden 3,072: a causal window of 512 as a
    trace-time mask (full layers: the causal mask, YaRN over half the
    head), the per-head gate, under ``jax.checkpoint`` inside the step's
    ``shard_map``."""
    from theanompi_tpu.models.routed_lm import rotary_frequencies
    mesh = one_chip_mesh
    with open("benchmarks/configs/laguna-s-2.1.json") as f:
        rope = json.load(f)["rope_parameters"][kind]
    freq, factor = rotary_frequencies(rope, 128)
    assert len(freq) == (64 if window else 32)
    attn = L.GroupedQueryAttention(3072, heads, 2, 128, freq, factor,
                                   window=window, attn_impl="flash",
                                   name="attn")
    params = jax.eval_shape(attn.init, jax.random.key(0))

    def per_worker(p, x):
        def loss(p, x):
            return jnp.sum(jax.checkpoint(attn.apply)(p, x[0]).astype(
                jnp.float32))
        return jax.tree.map(lambda g: g[None], jax.value_and_grad(loss)(
            jax.tree.map(lambda a: a[0], p), x))

    spec = P("workers")
    sh = NamedSharding(mesh, spec)
    boxed = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (1,) + a.shape, a.dtype, sharding=sh), params)
    x = jax.ShapeDtypeStruct((1, 1, 8192, 3072), jnp.float32, sharding=sh)
    step = jax.jit(shard_map(per_worker, mesh=mesh, in_specs=(spec, spec),
                             out_specs=spec))
    text = step.lower(boxed, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "splash_mha_fwd_residuals" in text
    assert "splash_mha_dkv_no_residuals" in text
    # the key/value heads go in repeated to the query heads
    assert f"bf16[{heads},8192,128]" in text
    for name, size in L.flash_tiles(8192, window).items():
        assert f'{name}\\": {json.dumps(size)}' in text, name
    assert "attn_core" in text


@pytest.fixture(scope="module")
def held_experts_program(one_chip_mesh):
    """One routed layer at the routed cell's shapes (8 of 256 experts of
    width 1,024 over 8,192 tokens of 3,072, 10 a token, with the shared
    expert), its value and its gradients to the parameters and the input
    under ``jax.checkpoint`` inside the step's ``shard_map``, compiled."""
    from theanompi_tpu.parallel.moe import HeldExperts
    mesh = one_chip_mesh
    layer = HeldExperts(3072, 256, (0, 8), 10, 1024, 1024, 2.5, name="moe")
    assert layer.rows_at_once(8192) == 4096
    params = jax.eval_shape(layer.init, jax.random.key(0))

    def per_worker(p, x):
        def loss(p, x):
            return jnp.sum(jax.checkpoint(layer.apply)(p, x[0]))
        return jax.tree.map(lambda g: g[None], jax.value_and_grad(
            loss, argnums=(0, 1))(jax.tree.map(lambda a: a[0], p), x[0]))

    spec = P("workers")
    sh = NamedSharding(mesh, spec)
    boxed = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (1,) + a.shape, a.dtype, sharding=sh), params)
    x = jax.ShapeDtypeStruct((1, 1, 8192, 3072), jnp.float32, sharding=sh)
    step = jax.jit(shard_map(per_worker, mesh=mesh, in_specs=(spec, spec),
                             out_specs=spec))
    return step.lower(boxed, x).compile()


def test_the_held_experts_compile_at_the_routed_cells_shapes(
        held_experts_program):
    """Router, sort, the walk over the routed pairs with its grouped
    products, the shared expert, forward and backward."""
    text = held_experts_program.as_text()
    assert "moe/router" in text and "experts" in text
    assert "shared_expert" in text
    # one stretch of 4,096 rows at a time, never the worst case's 65,536
    assert "[4096,3072]" in text and "[65536," not in text
    assert held_experts_program.memory_analysis().temp_size_in_bytes \
        < 2 * 2 ** 30


def _computations(text):
    """``{name: its instruction lines}`` of a compiled module's text, and
    the entry computation's name."""
    found, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(2)
            found[name] = []
            entry = name if head.group(1) else entry
        elif line == "}":       # a kernel's attributes may span lines
            name = None
        elif name is not None:
            found[name].append(line)
    return found, entry


def test_a_weight_gradient_of_the_experts_is_written_once(
        held_experts_program):
    """The first stretch of each walk is straight-line and the loops hold
    what overflows it.  In the entry computation nothing produces an array
    of the stacked experts' float32 shape but the three weight-gradient
    products, which the backward loop takes as its first carry and the
    output takes from the loop: no fill, no add into a carry, no round
    trip through bfloat16.  The straight-line backward stretch makes eight
    grouped products (two of the forward's again, six of its own: the third
    forward product is not needed for the router weights' gradient), so
    with the forward stretch's three the entry holds eleven; the loops'
    bodies three and eight."""
    found, entry = _computations(held_experts_program.as_text())
    loops = [line for line in found[entry] if " while(" in line]
    bodies = {re.search(r'op_name="([^"]*)"', line).group(1):
              re.search(r"body=%?([\w.\-]+)", line).group(1)
              for line in loops}
    forward, backward = sorted(bodies, key=lambda path: "transpose(" in path)
    assert forward.endswith("jvp(moe)/while")
    assert backward.endswith("checkpoint/moe/while") and len(loops) == 2
    products = {name: sum("ragged_dot_tiling" in line for line in lines)
                for name, lines in found.items()}
    assert products[entry] == 11
    assert products[bodies[forward]] == 3
    assert products[bodies[backward]] == 8
    assert sum(products.values()) == 22
    stacked = re.compile(r" = f32\[8,(3072,1024|1024,3072)\]\S* ([\w\-]+)\(")
    made = [(m.group(2), "ragged_dot_tiling" in line)
            for line in found[entry] for m in [stacked.search(line)] if m]
    # the three products, the loop's results, and views of the parameters
    assert made.count(("custom-call", True)) == 3
    assert set(made) == {("custom-call", True), ("get-tuple-element", False),
                         ("bitcast", False)}, made
    # the transposed matrices are made behind a branch, in bfloat16
    assert sum(" conditional(" in line for line in found[entry]) == 1


@pytest.mark.parametrize("form", ["as it is", "transposes straight-line"])
def test_the_experts_update_stays_in_the_parameters_layout(
        topo, monkeypatch, form):
    """A cut-down step of the routed model through ``steps.build_train_step``:
    a dense layer and one routed layer at the routed cell's shapes (8 of 256
    experts of width 1,024 over 8,192 tokens of 3,072, the shared expert),
    with Adam over every parameter and the state aliased to the outputs, as
    the cell's step has them and one layer compiled alone has not.  As the
    program is, the entry computation copies no float32 array of the stacked
    experts' shape and lays none out but row-major: the transposed bfloat16
    matrices of the backward products are made behind ``moe._turned``'s
    branch.  With the three transposes straight-line (the control, which is
    what a step without the branch is) layout assignment transposes the
    float32 parameters at the top of the step and runs the experts' update
    in that layout, its moments and gradients copied into it and its results
    copied back: 21 copies here, 84 and 16 ms a step in the cell, 3.8%
    slower than before the walk was touched (PERF.md section 6, PR 40).
    Where the control stops showing them the compiler no longer needs the
    branch and ``_turned`` can lose it."""
    from theanompi_tpu.models.routed_lm import RoutedLM
    from theanompi_tpu.parallel import moe, steps
    from theanompi_tpu.parallel.exchanger import BSP_Exchanger

    if form != "as it is":
        monkeypatch.setattr(moe, "_turned", lambda experts, cd, any_pair: (
            jax.tree.map(lambda a: jnp.swapaxes(a.astype(cd), 1, 2),
                         experts)))
    mesh = Mesh(np.array(topo.devices[:1]), ("workers",))
    monkeypatch.setattr(steps, "_keep_collectives_apart", lambda: None)
    model = RoutedLM({
        "mesh": mesh, "size": 1, "rank": 0, "verbose": False,
        "batch_size": 1, "vocab": 256, "d_model": 3072, "head_dim": 128,
        "n_kv_head": 1, "n_layer": 2, "d_ff": 1024, "n_experts": 256,
        "experts_held": (0, 8), "top_k": 10, "expert_width": 1024,
        "shared_width": 1024, "window": 512, "seq_len": 8192,
        "layer_types": ("sliding_attention",) * 2,
        "mlp_layer_types": ("dense", "sparse"), "n_head_per_layer": (2, 2),
        "attn_impl": "flash", "synthetic_train": 2, "synthetic_val": 1,
        "synthetic_batches": 1})
    exchanger = BSP_Exchanger(model.config)
    exchanger.prepare(mesh, model)
    rows = NamedSharding(mesh, P("workers"))
    whole = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype,
                                       sharding=rows),
        jax.eval_shape(lambda p: {
            "params": p, "opt_state": model.opt.init(p),
            "bn_state": model.bn_state,
            "extra": exchanger.extra_state_template()}, model.params))
    batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows),
        model._peek_batch_aval())
    scalars = [jax.ShapeDtypeStruct((), dtype, sharding=whole) for dtype in
               (jnp.float32, jax.random.key(0).dtype, jnp.int32)]
    step = steps.build_train_step(mesh, model, exchanger)
    found, entry = _computations(
        step.lower(state, batch, *scalars).compile().as_text())
    stacked = re.compile(r" = \(?f32\[(?:1,)?8,(?:3072,1024|1024,3072)\]"
                         r"\{([\d,]+)\S* ([\w\-]+)\(")
    made = [m.groups() for line in found[entry]
            for m in [stacked.search(line)] if m]
    # the parameters, two moments and the results of three matrices at least
    assert len(made) >= 18, made
    copies = [layout for layout, opcode in made if opcode == "copy"]
    turned = [layout for layout, _ in made
              if layout not in ("2,1,0", "3,2,1,0")]
    branches = sum(" conditional(" in line for line in found[entry])
    if form == "as it is":
        assert (copies, turned, branches) == ([], [], 1)
    else:
        assert len(copies) >= 9 and len(turned) >= 9 and branches == 0, (
            "the compiler leaves the experts' update alone without the "
            "branch: moe._turned may not need it any more", made)


def test_the_heads_make_three_products_a_block_and_no_backward_loop(
        one_chip_mesh):
    """``layers.weighted_cross_entropy`` at the looped cell's head shapes
    (4 x 8,192 tokens of 2,048, 49,152 classes, blocks of 2,048) with its
    gradients, inside the step's ``shard_map``: one loop whose body holds
    the logits, the gradient to the states and the share of the gradient
    to the weight, and nothing of the head under ``transpose(`` but the
    three scalings.  The program's temporaries read 1,007.3 MB (reverse
    mode over a rematerialised block, as the heads were until PR 38,
    747.3 MB: the states' gradient is held in bfloat16 from the loop to
    the backward pass, 134 MB, and a block's ``c (softmax - onehot)`` is
    written out in bfloat16 for the two products that read it, 201 MB)."""
    mesh = one_chip_mesh
    m, d, v, blk = 4 * 8192, 2048, 49152, 2048

    def per_worker(w, h, y, c):
        def loss(w, h, c):
            with jax.named_scope("exit_head"):
                return 3.7 * L.weighted_cross_entropy(
                    w, h, y[0], c, block=blk,
                    compute_dtype=jnp.bfloat16)[0]
        return jax.tree.map(lambda g: g[None], jax.value_and_grad(
            loss, argnums=(0, 1, 2))(w[0], h[0], c[0]))

    spec = P("workers")
    sh = NamedSharding(mesh, spec)
    args = [jax.ShapeDtypeStruct((1,) + shape, dtype, sharding=sh)
            for shape, dtype in (((d, v), jnp.float32), ((m, d), jnp.float32),
                                 ((m,), jnp.int32), ((m,), jnp.float32))]
    step = jax.jit(shard_map(per_worker, mesh=mesh, in_specs=(spec,) * 4,
                             out_specs=spec))
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    in_the_loop = [line for line in text.splitlines()
                   if " convolution(" in line
                   and "jvp(exit_head)/while/body" in line]
    assert len(in_the_loop) == L.head_logit_products(blk, blk) == 3
    # the logits and the weight's share come out [2048, 49152] in float32,
    # the states' gradient is the product that contracts the classes
    assert sum("= f32[2048,49152]" in line for line in in_the_loop) == 2
    assert sum("= f32[2048,2048]" in line for line in in_the_loop) == 1
    assert text.count(" while(") == 1
    assert "transpose(jvp(exit_head))/while" not in text
    assert "transpose(jvp(exit_head))/mul" in text      # the scalings
    assert compiled.memory_analysis().temp_size_in_bytes < 1050 * 10 ** 6


def _without_source_names(text):
    """The compiled module's text less what names its source: every
    ``metadata={...}`` and the four tables their ``stack_frame_id``s point
    into."""
    text = re.sub(r",?\s*metadata=\{[^{}]*\}", "", text)
    for table in ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames"):
        text = re.sub(r"\n%s\n.*?\n\n" % table, "\n\n", text, count=1,
                      flags=re.S)
    return text


def test_the_scopes_change_no_instruction_of_the_train_program(
        topo, monkeypatch):
    """The BSP train step of a small ``Sequential`` model (the CIFAR-10
    stack: convolutions, pools before their ReLUs, dropout, two FC layers)
    compiled for the described 2x2 mesh, as the program writes it and with
    ``jax.named_scope`` silenced: less its metadata the text is the same,
    so ``exchange``, ``update`` and the layers' keys cost the chip nothing.
    The scopes have no switch in the program; the test takes them out."""
    import contextlib

    from theanompi_tpu.models.cifar10 import Cifar10_model
    from theanompi_tpu.parallel import steps
    from theanompi_tpu.parallel.exchanger import BSP_Exchanger

    mesh = Mesh(np.array(topo.devices), ("workers",))
    n = mesh.shape["workers"]
    # the CPU backend's compiler option means nothing to the chip's
    monkeypatch.setattr(steps, "_keep_collectives_apart", lambda: None)
    model = Cifar10_model({
        "mesh": mesh, "size": n, "rank": 0, "verbose": False,
        "batch_size": 8, "n_class": 10, "synthetic_batches": 1,
        "synthetic_train": 64, "synthetic_val": 32})
    exchanger = BSP_Exchanger(model.config)
    exchanger.prepare(mesh, model)
    exchanger.gather_min_bytes = 0      # fc1's gradient as gathered operands
    rows = NamedSharding(mesh, P("workers"))
    whole = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype,
                                       sharding=rows),
        jax.eval_shape(lambda p: {
            "params": p, "opt_state": model.opt.init(p),
            "bn_state": model.bn_state,
            "extra": exchanger.extra_state_template()}, model.params))
    batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows),
        model._peek_batch_aval())
    scalars = [jax.ShapeDtypeStruct((), dtype, sharding=whole) for dtype in
               (jnp.float32, jax.random.key(0).dtype, jnp.int32)]

    def compiled_text():
        step = steps.build_train_step(mesh, model, exchanger)
        return step.lower(state, batch, *scalars).compile().as_text()

    scoped = compiled_text()
    for scope in ("/exchange/", "/update/", "jvp(conv1)/", "(jvp(fc1))/"):
        assert scope in scoped, scope
    assert "all-reduce" in scoped and "all-gather" in scoped
    # the reshape that boxes the new parameters and moments is the update's:
    # this compiler names an update's fusion after that root
    assert "/update/broadcast_in_dim" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled_text()
    assert "/exchange/" not in bare and "/update/" not in bare
    assert "jvp(conv1)" not in bare
    assert _without_source_names(scoped) == _without_source_names(bare)
