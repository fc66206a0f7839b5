"""The kernels of `ops/masked_flash.py` — indexed sparse attention's, and
the causal and sliding-window ones of `GatedAttention` — compiled by
Mosaic for a described TPU v5e at the `keye`, `trinity`, `qwen3`,
`smallthinker` and `lfm2` cells' own widths — nothing runs, no chip is needed: what the
interpreter cannot show (a tile Mosaic refuses, more fast memory than a
kernel may use), and the names the device trace will carry.

The topology is described inside a fixture, never at import, and every
such compile of the repo lives in this one file
(/opt/skills/guides/on-chip-measurement, section 2)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001  no TPU compiler on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def quiet_cache():
    """A described compile is written to the persistent cache but cannot
    be read back without a chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("keys", [2048, 16384])  # the cell's first and last run
def test_a_block_of_the_cell_compiles_and_its_kernels_bear_their_scopes(one_chip, quiet_cache, keys):
    """One block as the layer runs it — forward, its rematerialised
    forward and its backward under `jax.checkpoint` — is five Mosaic
    calls (the forward twice, the shares twice, the backward once), each
    named by `euler.dsa.core` or `euler.dsa.aux` alone, the backward's
    under `transpose(`: `benchmarks/scoped.py` finds a layer's device
    time by exactly that. No float32 tensor of a block's scores is left
    in the program."""
    from euler_tpu.ops import seq_ops
    from euler_tpu.utils import trace

    shape = lambda s, t=jnp.float32: jax.ShapeDtypeStruct(s, t, sharding=one_chip)  # noqa: E731
    q, kv = shape((1, 4, 8, 512, 128)), shape((1, 4, keys, 128))
    keep, scores = shape((1, 512, keys), jnp.bool_), shape((1, 512, keys))
    assert seq_ops.attends_by_tiles(q, kv)

    @jax.checkpoint
    def block(q, k, v, keep, scores):
        with trace.scope("dsa.core"):
            o, lse = seq_ops.masked_attention(q, k, v, keep, 128**-0.5)
        with trace.scope("dsa.aux"):
            p = seq_ops.attention_share(q, k, keep, lse, 128**-0.5)
            return o, seq_ops.index_kl(p, scores, keep)

    def loss(q, k, v, keep, scores):
        o, kl = block(q, k, v, keep, scores)
        return jnp.sum(jnp.sin(o)) + kl

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 4)))
    text = step.lower(q, kv, kv, keep, scores).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    kernels = sorted(name.split("/")[-2] for name in calls)
    assert kernels == ["dsa_aux_share"] * 2 + ["dsa_core_backward"] + ["dsa_core_forward"] * 2
    for name in calls:
        scopes = re.findall(r"euler\.([a-z_.]+)", name)
        assert scopes == ["dsa.aux" if "dsa_aux" in name else "dsa.core"], name
        assert ("transpose(" in name) == ("jvp(euler" not in name), name
    assert not re.findall(rf"f32\[1,4,8,512,{keys}\]", text)


@pytest.mark.parametrize(
    "batch,groups,heads,length,head_dim,window",
    # `trinity`'s window layers and its full layer; `qwen3`'s attention
    # layer; `smallthinker`'s window layers (7 query heads a key head, a
    # tile of queries given 9 tiles of keys) and its full layer; `lfm2`'s
    # full layer at a head of 64 (the kernels' blocks end in an extent of
    # 64), and that head under a window
    [
        (1, 4, 8, 16384, 128, 2048), (1, 4, 8, 16384, 128, None), (2, 2, 8, 8192, 256, None),
        (1, 4, 7, 16384, 128, 4096), (1, 4, 7, 16384, 128, None),
        (1, 8, 4, 16384, 64, None), (1, 8, 4, 16384, 64, 4096),
    ],
)
def test_a_layers_causal_core_compiles_and_its_kernels_bear_their_scope(
    one_chip, quiet_cache, batch, groups, heads, length, head_dim, window
):
    """A layer's core over the whole sequence, forward and backward, is
    three Mosaic calls within the kernels' limit of fast memory, each
    named by `euler.swa.core` (`euler.attn.core` without a window) alone,
    the two backward ones under `transpose(`: `swa_ms`, `attn_ms` and the
    two roofline readers sum device time by exactly that. No float32
    tensor of a block's scores is left in the program."""
    from euler_tpu.ops import seq_ops
    from euler_tpu.utils import trace

    shape = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    q, kv = shape((batch, groups, heads, length, head_dim)), shape((batch, groups, length, head_dim))
    assert seq_ops.causal_tile(q, 512) == 512
    scope = "attn.core" if window is None else "swa.core"

    def loss(q, k, v):
        with trace.scope(scope):
            o = seq_ops.blockwise_causal_attention(q, k, v, head_dim**-0.5, 512, window)
        return jnp.sum(jnp.sin(o))

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    text = step.lower(q, kv, kv).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    kernels = sorted(name.split("/")[-2] for name in calls)
    assert kernels == ["causal_core_dkv", "causal_core_dq", "causal_core_forward"]
    for name in calls:
        assert re.findall(r"euler\.([a-z_.]+)", name) == [scope], name
        assert ("transpose(" in name) == ("causal_core_forward" not in name), name
    assert not re.findall(rf"f32\[{batch},{groups},{heads},512,\d+\]", text)


def test_the_delta_rules_kernels_compile_once_and_their_bodies_do_not_grow_with_the_sequence(
    one_chip, quiet_cache
):
    """`qwen3`'s delta rule at the cell's widths (q, k, v [2, 32, T, 128],
    chunk 64, groups of 4): value and gradient are two Mosaic calls
    (`ops/delta_rule_kernels.py`), named by `euler.gdn.scan` alone, the
    backward's under `transpose(`, which is how `gdn_ms` finds them, and
    no `while` of the rule is left in the program. A kernel's body is one
    group of one head: its lowered text is as long at T 2,048 as at
    8,192, so its trace and lowering do not grow with the sequence; and
    three layers' calls at one shape lower each kernel once."""
    from euler_tpu.ops import seq_ops
    from euler_tpu.utils import trace

    def bodies(text):
        return re.findall(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)", text)

    lengths = {}
    for length in (2048, 8192):
        shape = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
        q, gate = shape((2, 32, length, 128)), shape((2, 32, length))
        assert seq_ops.delta_rule_by_kernels(q, q, 64)

        def loss(q, k, v, g, beta):
            with trace.scope("gdn.scan"):
                o = seq_ops.chunk_gated_delta_rule(q, k, v, g, beta, chunk=64, group=4)
            return jnp.sum(jnp.sin(o))

        lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            q, q, q, gate, gate
        )
        lengths[length] = [len(b) for b in bodies(lowered.as_text())]
        text = lowered.compile().as_text()
        calls = re.findall(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
        kernels = sorted(name.split("/")[-2] for name in calls)
        assert kernels == ["gdn_rule_backward", "gdn_rule_forward"]
        for name in calls:
            assert re.findall(r"euler\.([a-z_.]+)", name) == ["gdn.scan"], name
            assert ("transpose(" in name) == ("gdn_rule_backward" in name), name
        assert " while(" not in text
    assert len(lengths[2048]) == 2 and lengths[2048] == lengths[8192]

    def three_layers(q, k, v, g, beta):
        return sum(
            jnp.sum(jnp.sin(seq_ops.chunk_gated_delta_rule(q * s, k, v, g, beta)))
            for s in (1.0, 2.0, 3.0)
        )

    text = jax.jit(jax.value_and_grad(three_layers, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, q, gate, gate
    ).as_text()
    assert len(bodies(text)) == 2
