"""The bin kernels of the main path, compiled for a described TPU v5e at a
cell's own size (no chip needed: the TPU compiler is installed, nothing
runs).  What the compiler does with the planes is the point of these
tests: on the chip a scatter or a gather over a plane in the wrong form
was a copy of the whole plane in every dispatch (PERF.md section 6, PR 33),
and no CPU run shows that.

One file, and the topology described inside a fixture: only one process
may hold the TPU's library, and the workers of a parallel run each import
every test file."""

import re

import pytest

C, B, W = 1 << 21, 8, 1  # nexmark_q8.catchup's state


@pytest.fixture(scope="module")
def on_chip():
    """``on_chip(dims, dtype)``: the shape of an argument that lies on one
    described chip."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _plane_sized_ops(text, plane=B * C):
    """(instruction, opcode) of the entry computation's instructions whose
    result is as large as a plane, parameters and views aside."""
    entry = text[text.index("ENTRY"):]
    found = []
    for name, shape, op in re.findall(
            r"^\s+(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", entry, re.M):
        dims = re.search(r"\[([\d,]*)\]", shape)
        size = 1
        for d in (dims.group(1).split(",") if dims and dims.group(1)
                  else []):
            size *= int(d)
        if size >= plane and op not in (
                "parameter", "bitcast", "get-tuple-element", "tuple"):
            found.append((name, op))
    return found


def test_update_scatters_into_the_planes_it_is_given(on_chip):
    import jax.numpy as jnp

    from arroyo_tpu.ops import keyed_bins

    n = 65536
    compiled = keyed_bins._update_kernel(("count",), C, B, n, (0,)).lower(
        (on_chip((B * C,), jnp.float64),), on_chip((B * C,), jnp.int32),
        on_chip((2, n), jnp.int32), on_chip((1, n), jnp.float64)).compile()
    text = compiled.as_text()
    # no loop that lays a plane out anew, slice by slice, and no copy
    assert " while(" not in text
    ops = _plane_sized_ops(text)
    assert not [o for o in ops if o[1] in ("copy", "dynamic-update-slice",
                                           "broadcast")], ops
    # both planes are written where they lie
    assert compiled.memory_analysis().alias_size_in_bytes == B * C * (8 + 4)


def test_fire_scan_reads_the_counts_where_they_lie(on_chip):
    import jax.numpy as jnp

    from arroyo_tpu.ops import keyed_bins

    compiled = keyed_bins._emit_count_kernel(C, B, W, 1).lower(
        on_chip((B * C,), jnp.int32), on_chip((1, W), jnp.int32),
        on_chip((1, W), jnp.bool_)).compile()
    text = compiled.as_text()
    assert " while(" not in text
    assert not _plane_sized_ops(text)


def test_hop_scan_reads_five_bin_rows_of_sixteen(on_chip):
    """``nexmark_q5_counts.catchup``'s scan, C = 2^22 slots, a ring of 16
    bins, HOP(2 s, 10 s): the five bin rows of the window are gathered from
    the counts where they lie.  Nothing as large as the plane is made, no
    loop lays it out anew, and what the program holds beside its arguments
    is the gathered rows and the sort's, well under the plane's 268 MB."""
    import jax.numpy as jnp

    from arroyo_tpu.ops import keyed_bins

    c, b, w = 1 << 22, 16, 5
    compiled = keyed_bins._emit_count_kernel(c, b, w, 1).lower(
        on_chip((b * c,), jnp.int32), on_chip((1, w), jnp.int32),
        on_chip((1, w), jnp.bool_)).compile()
    text = compiled.as_text()
    assert " while(" not in text
    assert not _plane_sized_ops(text, b * c)
    assert compiled.memory_analysis().temp_size_in_bytes < b * c * 4


def test_top_k_compiles_at_a_windows_size_without_a_sort(on_chip):
    """``nexmark_topn_price.catchup``'s selection, 600,000 rows in the
    bucket 2^20: the segment top-k is scans over ``[1024, 1024]`` arrays
    inside one loop over k, and no ``sort``.  A ``lax.sort`` on (segment,
    value, row) keys took this compiler 38 s at 2^14 rows and 200 s at
    2^16 (PERF.md section 6, PR 36), so what is held here is that the
    program compiles at all inside a test's time, and stays small."""
    import jax.numpy as jnp

    from arroyo_tpu.ops import topk

    n_pad = 1 << 20
    lines = topk._lines(n_pad)
    compiled = topk._topk_kernel(n_pad).lower(
        on_chip(lines, jnp.int8), on_chip(lines, jnp.int32),
        on_chip(lines, jnp.int32), on_chip((), jnp.int32)).compile()
    text = compiled.as_text()
    assert " sort(" not in text
    assert text.count(" while(") == 1  # the k passes, and no other loop
    # the three inputs are 9 MB; the passes keep a few such arrays
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
