"""Compile-only checks, for a described (not attached) TPU v5e, of the
attention forward and gradient and of the delta rule's gradient at the LM
cells' sizes. Nothing runs: a compile that
passes here is not a chip run. The fixture skips, as
tests/bench_yardstick/test_compile_v5e.py does, where no topology can be
described (another process of the run may hold the TPU's compiler)."""
import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the description
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around these."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_attention_gradient_keeps_no_scores_at_the_lm_cells_size(one_chip,
                                                                 no_cache):
    """opt-1.3b-fit-s1024: batch 4, 32 heads of 64, 1024 tokens, bf16. The
    gradient is the forward kernel and the backward kernel, and nothing of
    (B*H, T, T) is written to HBM."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import attention
    q = jax.ShapeDtypeStruct((4, 32, 1024, 64), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(attention.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "[128,1024,1024]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.35e9


def test_attention_gradient_compiles_at_the_hybrid_cells_size(one_chip,
                                                              no_cache):
    """olmo-hybrid-7b-fit-s2048's full-attention layer: batch 4, 30 heads of
    128, 2048 tokens, bf16: both kernels, by their names, and no scores."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import attention
    q = jax.ShapeDtypeStruct((4, 30, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(attention.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    assert "%" + attention.FWD_KERNEL_NAME in text
    assert "%" + attention.BWD_KERNEL_NAME in text
    assert "[120,2048,2048]" not in text


@pytest.mark.parametrize("b,h,t,d", [(4, 32, 1024, 64), (4, 30, 2048, 128)],
                         ids=["opt-1.3b-fit-s1024",
                              "olmo-hybrid-7b-fit-s2048"])
@pytest.mark.parametrize("with_lse", [False, True])
def test_walked_forward_compiles_at_the_cells_sizes(one_chip, no_cache, b, h,
                                                    t, d, with_lse):
    """Both LM cells' attention shapes tile, so their forward is the kernel
    that walks its key blocks, a head's queries in one block: one Mosaic
    call by the forward's name, with and without the log-sum-exp output,
    inside the scoped VMEM it asks for (Mosaic refuses a kernel that does
    not fit)."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import attention
    assert attention._fwd_blocks(t, t, d, 2, True) == (t, 512)
    q = jax.ShapeDtypeStruct((b * h, t, d), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda q, k, v: attention._forward(
        q, k, v, d ** -0.5, True, 0, 0, with_lse)).lower(
            q, q, q).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%" + attention.FWD_KERNEL_NAME in text
    assert ("f32[%d,1,1,%d]" % (b * h, t) in text) == with_lse


def test_delta_rule_gradient_compiles_at_the_hybrid_cells_size(one_chip,
                                                               no_cache):
    """olmo-hybrid-7b-fit-s2048's linear-attention layers: batch 4, 30 heads,
    d_k 96, d_v 192, 2048 tokens, bf16. Two Mosaic calls found by name, one
    of each; the chunk-boundary states and the chunks' triangular inverses
    (four of a grid step side by side, bfloat16) go from the forward call to
    the backward call, and no per-token state (2048 states of 96 x 192 a
    head) is anywhere."""
    import re
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import delta_rule

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (s((4, 30, 2048, 96)), s((4, 30, 2048, 96)), s((4, 30, 2048, 192)),
            s((4, 30, 2048), jnp.float32), s((4, 30, 2048)))

    def loss(*a):
        return jnp.sum(delta_rule.gated_delta_rule(*a).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    text = compiled.as_text()
    calls = {name: re.findall(
        r"^\s*(%%%s[\w.]*) = \((.*?)\) custom-call\((.*?)\), custom_call_target"
        % name, text, re.M)
        for name in (delta_rule.FWD_KERNEL_NAME, delta_rule.BWD_KERNEL_NAME)}
    (fwd, fwd_results, _), = calls[delta_rule.FWD_KERNEL_NAME]    # one call
    (_, _, bwd_operands), = calls[delta_rule.BWD_KERNEL_NAME]     # of each
    # o, one state a chunk, one inverse a chunk: 120 x 8 x 4 of 64 x 64
    assert [r.split("{")[0] for r in fwd_results.split(", ")] == [
        "bf16[120,2048,192]", "f32[120,32,96,192]", "bf16[120,8,64,256]"]
    bwd_operands = re.sub(r"/\*.*?\*/", "", bwd_operands).split(", ")
    for index in (1, 2):
        element, = re.findall(
            r"(%%[\w.-]+) = \S+ get-tuple-element\(%s\), index=%d"
            % (re.escape(fwd), index), text)
        assert element in bwd_operands, (index, element, bwd_operands)
    assert "2048,96,192]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


@pytest.mark.parametrize("heads,window", [(48, 0), (72, 512)],
                         ids=["full-48-over-8", "window-72-over-8"])
def test_grouped_attention_gradient_compiles_at_the_laguna_cells_size(
        one_chip, no_cache, heads, window):
    """laguna-s-2.1-fit-s4096's two attention kinds: batch 2, 48 (full) or
    72 (window 512) query heads over 8 key/value heads of 128, 4096 tokens,
    bf16. One forward and one backward Mosaic call, by the kind's names; K
    and V go in as 16 heads and dK, dV come out as 16, so nothing is
    repeated to the query heads in HBM; no (T, T) buffer."""
    import re
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import attention
    b, g, t, d = 2, 8, 4096, 128
    q = jax.ShapeDtypeStruct((b, heads, t, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, g, t, d), jnp.bfloat16, sharding=one_chip)
    assert attention._fwd_blocks(t, t, d, 2, True, 0, 0, window) == (
        (512, 512) if window else (2048, 512))
    assert attention._bwd_blocks(t, t, d, 2, True, window,
                                 heads // g) == (512, 512)

    def loss(q, k, v):
        return jnp.sum(attention.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    fwd, bwd = ((attention.WIN_FWD_KERNEL_NAME, attention.WIN_BWD_KERNEL_NAME)
                if window else
                (attention.FWD_KERNEL_NAME, attention.BWD_KERNEL_NAME))
    per_q, per_kv = "bf16[%d,4096,128]" % (b * heads), "bf16[16,4096,128]"
    for name, operands in ((fwd, [per_q, per_kv, per_kv]),
                           (bwd, [per_q, per_kv, per_kv, per_q])):
        (line,) = [ln for ln in text.splitlines()
                   if re.match(r"\s*%%%s[\w.]* = " % name, ln)]
        layouts = line.split("operand_layout_constraints={", 1)[1]
        got = re.findall(r"[a-z0-9]+\[[\d,]*\]", layouts)
        assert got[:len(operands)] == operands, (name, got)
    # dQ per query head, dK and dV per key/value head, from the one call
    (results,) = re.findall(r"%%%s[\w.]* = \((.*?)\) custom-call" % bwd, text)
    assert [r.split("{")[0] for r in results.split(", ")] == [
        per_q, per_kv, per_kv]
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert "4096,4096]" not in text
    # q, k, v, o, dO, the gradients and the float32 rows: no copy of K or V
    # at the query heads' count beside them
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * (
        b * heads * t * d * 2)


_LAGUNA_ROPE = {
    # the window layers': every dim, paired (i, i + 64)
    "default": dict(rotary_dims=0, rope_type="default", theta=10000.0),
    # the full layers': YaRN over the first 64 dims, paired (i, i + 32)
    "yarn": dict(rotary_dims=64, rope_type="yarn", theta=500000.0,
                 factor=32.0, original_max_position=4096, beta_fast=32.0,
                 beta_slow=1.0, scale=1.4852),
}


@pytest.mark.parametrize("heads,kind", [(72, "default"), (48, "yarn"),
                                        (8, "default")],
                         ids=["window-q-72", "full-q-48", "k-8"])
def test_head_prep_gradient_compiles_at_the_laguna_cells_size(
        one_chip, no_cache, heads, kind):
    """laguna-s-2.1-fit-s4096's head preparation: batch 2, 4096 tokens, 72
    or 48 query heads or the 8 key/value heads of 128, bf16, both rotary
    kinds. One forward and one backward Mosaic call by their names; the
    projection goes in as (B, T, n dh) and the kernel's operand comes out
    as (B, n, T, dh), and back; no float32 buffer of a whole q, either
    way round, is anywhere."""
    import re
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import heads as hd
    b, t, dh = 2, 4096, 128
    x = jax.ShapeDtypeStruct((b, t, heads * dh), jnp.bfloat16,
                             sharding=one_chip)
    gamma = jax.ShapeDtypeStruct((dh,), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((b, heads, t, dh), jnp.bfloat16,
                             sharding=one_chip)
    assert hd._prep_blocks(t, heads, dh, 2) == (512, 8)

    def both(x, gamma, w):
        out, vjp = jax.vjp(lambda x, gamma: hd.head_norm_rotary(
            x, gamma, heads, 1e-6, **_LAGUNA_ROPE[kind]), x, gamma)
        return (out,) + vjp(w)

    text = jax.jit(both).lower(x, gamma, w).compile().as_text()
    flat, by_head = ("bf16[2,4096,%d]" % (heads * dh),
                     "bf16[2,%d,4096,128]" % heads)
    (fwd,) = re.findall(r"%%%s[\w.]* = (\S+?)\{.*? custom-call"
                        % hd.PREP_FWD_KERNEL_NAME, text)
    assert fwd == by_head
    (bwd,) = re.findall(r"%%%s[\w.]* = \((.*?)\) custom-call"
                        % hd.PREP_BWD_KERNEL_NAME, text)
    assert [r.split("{")[0] for r in bwd.split(", ")] == [
        flat, "f32[2,8,8,128]"]
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert not re.findall(r"f32\[2,(?:%d,4096|4096,%d),128\]"
                          % (heads, heads), text)
    assert "f32[2,4096,%d]" % (heads * dh) not in text


@pytest.mark.parametrize("heads", [72, 48])
def test_head_gate_gradient_compiles_at_the_laguna_cells_size(
        one_chip, no_cache, heads):
    """The gate of a window and of a full layer: the kernel's output goes in
    as (B, H, T, dh) and what the output projection reads comes out as
    (B, T, H dh); the backward returns dAtt in the kernel's layout and the
    logits' gradient as float32 rows."""
    import re
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import heads as hd
    b, t, dh = 2, 4096, 128
    att = jax.ShapeDtypeStruct((b, heads, t, dh), jnp.bfloat16,
                               sharding=one_chip)
    g = jax.ShapeDtypeStruct((b, t, heads), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((b, t, heads * dh), jnp.bfloat16,
                             sharding=one_chip)

    def both(att, g, w):
        out, vjp = jax.vjp(hd.head_gate, att, g)
        return (out,) + vjp(w)

    text = jax.jit(both).lower(att, g, w).compile().as_text()
    (fwd,) = re.findall(r"%%%s[\w.]* = (\S+?)\{.*? custom-call"
                        % hd.GATE_FWD_KERNEL_NAME, text)
    assert fwd == "bf16[2,4096,%d]" % (heads * dh)
    (bwd,) = re.findall(r"%%%s[\w.]* = \((.*?)\) custom-call"
                        % hd.GATE_BWD_KERNEL_NAME, text)
    assert [r.split("{")[0] for r in bwd.split(", ")] == [
        "bf16[2,%d,4096,128]" % heads, "f32[2,4096,%d]" % heads]
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert not re.findall(r"f32\[2,(?:%d,4096|4096,%d),128\]"
                          % (heads, heads), text)


def test_ssd_gradient_compiles_at_the_nemotron_cells_size(one_chip, no_cache):
    """nemotron-twotower-30b-fit-s4096's Mamba-2 layers: batch 2, 64 heads
    of 64 in 8 groups of state 128, 4096 tokens in chunks of 128, bf16. Two
    Mosaic calls found by name, one of each; x and y stay (B, T, H P) and B
    and C (B, T, G N), so nothing is repeated to the heads or transposed in
    HBM; the chunk-first states, float32, are the one residual the forward
    call hands the backward call; no `while` at the level of XLA and no
    per-token state (4096 states of 128 x 64 a head) anywhere."""
    import re
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import ssd

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (s((2, 4096, 64, 64)), s((2, 4096, 64), jnp.float32),
            s((64,), jnp.float32), s((2, 4096, 8, 128)),
            s((2, 4096, 8, 128)), s((64,), jnp.float32))

    def loss(*a):
        return jnp.sum(ssd.ssd_scan(*a).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *args).compile()
    text = compiled.as_text()
    calls = {name: re.findall(
        r"^\s*(%%%s[\w.]*) = \((.*?)\) custom-call\((.*?)\), custom_call_target"
        % name, text, re.M)
        for name in (ssd.FWD_KERNEL_NAME, ssd.BWD_KERNEL_NAME)}
    (fwd, fwd_results, _), = calls[ssd.FWD_KERNEL_NAME]     # one call
    (_, bwd_results, bwd_operands), = calls[ssd.BWD_KERNEL_NAME]    # of each
    # y, and one state a chunk a group: 2 x 8 x 32 of 128 x 512
    assert [r.split("{")[0] for r in fwd_results.split(", ")] == [
        "bf16[2,4096,4096]", "f32[2,8,32,128,512]"]
    # dx; ddt and dcs as rows; dB and dC a group; dD's row a batch a group
    bwd_results = re.sub(r"/\*.*?\*/", "", bwd_results)
    assert [r.split("{")[0] for r in bwd_results.split(", ")] == [
        "bf16[2,4096,4096]", "f32[2,8,32,8,128]", "f32[2,8,32,8,128]",
        "bf16[2,4096,1024]", "bf16[2,4096,1024]", "f32[2,8,1,512]"]
    states, = re.findall(
        r"(%%[\w.-]+) = \S+ get-tuple-element\(%s\), index=1"
        % re.escape(fwd), text)
    assert states in re.sub(r"/\*.*?\*/", "", bwd_operands).split(", ")
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert not re.findall(r"^\s*%?while", text, re.M)
    assert "4096,128,64]" not in text and "4096,64,128]" not in text
    # x, y, dy, dx, B, C and their gradients, the states (134 MB) and rows
    assert compiled.memory_analysis().temp_size_in_bytes < 0.35e9


def test_ssd_forward_alone_keeps_no_states(one_chip, no_cache):
    """Outside differentiation the forward call writes y alone."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import ssd

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(ssd.ssd_scan).lower(
        s((2, 4096, 64, 64)), s((2, 4096, 64), jnp.float32),
        s((64,), jnp.float32), s((2, 4096, 8, 128)), s((2, 4096, 8, 128)),
        s((64,), jnp.float32)).compile().as_text()
    assert "%" + ssd.FWD_KERNEL_NAME in text
    assert "%" + ssd.BWD_KERNEL_NAME not in text
    assert "f32[2,8,32,128,512]" not in text


_EXPERT_CELLS = {
    "laguna-s-2.1-fit-s4096": (3072, 1024, 10, 256, True),
    "nemotron-twotower-30b-fit-s4096": (2688, 1856, 6, 128, False)}
_expert_texts = {}


def _expert_layer_text(cell, one_chip):
    """The compiled value and gradient of the expert layer at a cell's
    sizes: 8,192 tokens, 8 experts held, bf16, trips of 4,096 rows (one
    compile a cell for the tests below), and the combines it counted."""
    if cell not in _expert_texts:
        import jax
        import jax.numpy as jnp
        from mxtpu.ops import moe
        d, f, k, experts, gated = _EXPERT_CELLS[cell]

        def s(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        ups = [s((8, f, d))] * (2 if gated else 1)

        def loss(x, tw, ups, wd, ti, cot):
            out, _ = moe.moe_experts(x, tw, ti, ups[0] if gated else None,
                                     ups[-1], wd, experts, 0)
            return jnp.sum(out.astype(jnp.float32) * cot)

        before = _builds("moe_combine_builds")
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
            s((8192, d)), s((8192, k), jnp.float32), ups, s((8, d, f)),
            s((8192, k), jnp.int32), s((8192, d), jnp.float32)
        ).compile().as_text()
        after = _builds("moe_combine_builds")
        _expert_texts[cell] = text, {
            p: after[p] - before.get(p, 0) for p in after}
    return _expert_texts[cell]


def _computations(text):
    """{computation name ("entry" for the entry): its body}."""
    import re
    return {("entry" if head.startswith("ENTRY") else head): body
            for head, body in re.findall(
                r"^((?:ENTRY )?%[\w.-]+) \(.*?\n(.*?)^\}", text,
                re.M | re.S)}


@pytest.mark.parametrize(
    "cell,leaves,fwd,bwd",
    [("laguna-s-2.1-fit-s4096", ("f32[8,1024,3072]", "f32[8,3072,1024]"),
      3, 8),
     ("nemotron-twotower-30b-fit-s4096",
      ("f32[8,2048,2688]", "f32[8,2688,2048]"), 2, 5)],
    ids=["laguna-s-2.1-fit-s4096", "nemotron-twotower-30b-fit-s4096"])
def test_expert_layer_takes_its_first_trip_outside_the_loop(
        one_chip, no_cache, cell, leaves, fwd, bwd):
    """The expert layer and its gradient at the two cells' sizes: 8,192
    tokens, 8 experts held, bf16, trips of 4,096 rows. The grouped products
    of the forward and of the backward stand in the entry computation (the
    first trip) as well as in the loop body behind each, and the backward
    takes one product fewer than the walk once did (the down product is not
    recomputed for the router weight's gradient: 8 where the gated form had
    9, 5 where `relu2` had 6); the weight gradients are the first trip's
    products themselves, so no float32 zeros of a stacked leaf's shape are
    made for the loop to add to."""
    import re
    text, _ = _expert_layer_text(cell, one_chip)
    products = {}
    for head, body in _computations(text).items():
        calls = len(re.findall(r"^\s*%ragged-dot-none[\w.]* = ", body, re.M))
        if calls:
            products[head] = calls
    # both first trips; XLA may share the forward's gate and up products
    # with the backward's recomputed ones, which then stand there once
    assert bwd < products.pop("entry") <= fwd + bwd, products
    assert sorted(products.values()) == [fwd, bwd], products
    # the weight gradients: products' results, and the loop's sums of them
    made = re.findall(r"= (f32\[8,\d+,\d+\])\S* (\w[\w-]*)\(", text)
    assert {shape for shape, _ in made} == set(leaves)
    assert "broadcast" not in {op for _, op in made}, made


@pytest.mark.parametrize("cell", list(_EXPERT_CELLS))
def test_expert_layer_combines_without_a_scatter(one_chip, no_cache, cell):
    """The combine of the expert layer at the two cells' sizes writes each
    token's row once: no scatter of the trip's rows into an f32[8192,d] of
    the tokens' rows, forward or backward. Each direction's conditional in
    the entry computation (one trip, or more) has a branch of one combine
    kernel call and no loop, the call writing the layer's bf16[8192,d]
    (the cast rides in the call), and a branch with one call before its
    loop and one in the loop's body, in float32; the grouped products are
    the ones the test above counts, and the traced gradient counts its two
    combines as fused."""
    import re
    from mxtpu.ops import moe
    text, built = _expert_layer_text(cell, one_chip)
    d = _EXPERT_CELLS[cell][0]
    assert not re.search(r"= f32\[8192,%d\]\S* scatter\(" % d, text)
    assert built == {"fused": 2}, built
    comps = _computations(text)
    call = r"^\s*%%%s[\w.]* = (\w+)\[" % moe.COMBINE_KERNEL_NAME
    conds = re.findall(r" conditional\(.*?branch_computations=\{([^}]*)\}",
                       comps["entry"])
    assert len(conds) == 2, conds
    for branches in conds:
        alone, more = [b.strip() for b in branches.split(",")]
        assert re.findall(call, comps[alone], re.M) == ["bf16"]
        assert " while(" not in comps[alone]
        assert re.findall(call, comps[more], re.M) == ["f32"]
        body = re.search(r" while\(.*?body=(%[\w.-]+)", comps[more]).group(1)
        assert re.findall(call, comps[body], re.M) == ["f32"]
    assert len(re.findall(call, text, re.M)) == 6


def _block_gradient_text(mix, data_shape, one_chip):
    """The compiled gradient (every argument's) of sum(mix(data)) in
    bfloat16, the float32 decay parameters as the graph declares them."""
    import jax
    import jax.numpy as jnp
    import mxtpu as mx
    from mxtpu.executor import _trace_graph
    with mx.name.NameManager():
        net = mix(mx.sym.Variable("data"))
    names = net.list_arguments()
    shapes, _, _ = net.infer_shape(data=data_shape)
    types, _, _ = net.infer_type(
        **{n: "float32" if n.endswith(("A_log", "dt_bias", "_D"))
           else "bfloat16" for n in names})
    args = {n: jax.ShapeDtypeStruct(s, jnp.dtype(t), sharding=one_chip)
            for n, s, t in zip(names, shapes, types)}
    run = _trace_graph(net, is_train=True)

    def loss(args):
        (out,), _ = run(args, {}, None)
        return jnp.sum(out.astype(jnp.float32))

    return jax.jit(jax.grad(loss)).lower(args).compile().as_text()


def _entry(text):
    return text[text.index("\nENTRY "):]


def _builds(name):
    from mxtpu import telemetry
    return {m.labels.get("path"): m.value
            for m in telemetry.registry().series() if m.name == name}


def test_mamba2_block_keeps_no_float32_pass_at_the_nemotron_cells_size(
        one_chip, no_cache):
    """nemotron-twotower-30b-fit-s4096's mixer, differentiated: batch 2,
    4096 tokens, d 2688, 64 heads of 64 in 8 groups of state 128, bf16. The
    short convolution's backward and the gated group norm's two passes are
    Mosaic calls beside the scan's two; the entry computation holds no
    float32 buffer of an xBC (6144 channels) or of a y or z (4096)."""
    from mxtpu.models import decoder
    from mxtpu.ops import mixers, ssd
    before = _builds("mixer_conv_builds"), _builds("mixer_norm_builds")
    text = _block_gradient_text(
        lambda x: decoder.mamba2_mix(x, 4096, 2688, "l0", num_heads=64,
                                     head_dim=64, n_groups=8, state_size=128,
                                     norm_eps=1e-5),
        (2, 4096, 2688), one_chip)
    for name in (mixers.CONV_BWD_KERNEL_NAME, mixers.NORM_FWD_KERNEL_NAME,
                 mixers.NORM_BWD_KERNEL_NAME, ssd.FWD_KERNEL_NAME,
                 ssd.BWD_KERNEL_NAME):
        assert text.count("%%%s" % name) >= 1, name
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 5
    entry = _entry(text)
    assert "f32[2,4096,6144]" not in entry and "f32[2,4096,4096]" not in entry
    assert "f32[8192,4096]" not in entry
    after = _builds("mixer_conv_builds"), _builds("mixer_norm_builds")
    assert after[0].get("fused", 0) == before[0].get("fused", 0) + 1
    assert after[1].get("fused", 0) == before[1].get("fused", 0) + 1


def test_delta_rule_block_keeps_no_float32_pass_at_the_hybrid_cells_size(
        one_chip, no_cache):
    """olmo-hybrid-7b-fit-s2048's linear-attention mixer, differentiated:
    batch 4, 2048 tokens, d 3840, 30 heads of 96 / 192, bf16. All three
    short convolutions take the backward kernel (v's 5760 channels in blocks
    of three lane tiles, q's and k's 2880, 22.5 tiles, as whole rows); the
    gated norm's two passes take pairs of heads; the entry computation holds
    no float32 buffer of a v, an output or a gate (5760 channels)."""
    import re
    from mxtpu.models import decoder
    from mxtpu.ops import delta_rule, mixers
    before = _builds("mixer_conv_builds"), _builds("mixer_norm_builds")
    text = _block_gradient_text(
        lambda x: decoder.delta_rule_mix(x, 2048, 30, 3840, "l0", key_dim=96,
                                         value_dim=192),
        (4, 2048, 3840), one_chip)
    for name in (mixers.CONV_BWD_KERNEL_NAME, mixers.NORM_FWD_KERNEL_NAME,
                 mixers.NORM_BWD_KERNEL_NAME, delta_rule.FWD_KERNEL_NAME,
                 delta_rule.BWD_KERNEL_NAME):
        assert text.count("%%%s" % name) >= 1, name
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 7
    # (q's and k's L2 norms, no part of this, still hold theirs in float32)
    assert not re.findall(r"f32\[(?:4,2048,5760|4,2048,30,192|8192,5760)\]",
                          _entry(text))
    after = _builds("mixer_conv_builds"), _builds("mixer_norm_builds")
    assert after[0].get("fused", 0) == before[0].get("fused", 0) + 3
    assert after[0].get("composed", 0) == before[0].get("composed", 0)
    assert after[1].get("fused", 0) == before[1].get("fused", 0) + 1
