"""Record the small trace ``tests/data/flash_attention_v5e.xplane.pb`` on one
TPU chip, and print what ``harness/trace.py::reduce`` makes of it:

    python3 benchmarks/chip/tests/record_attention_trace.py \\
        --out benchmarks/chip/tests/data/flash_attention_v5e.xplane.pb

Inside one ``bench.window`` span: one launch of the program's non-causal
Pallas flash attention (``repro.kernels.flash_attention``) at ViT-B/16's
shape, q, k and v of (4, 741, 12, 64) in float32 (``bench.attention``),
then the same attention as plain XLA einsums and a softmax
(``bench.plain``), so the trace shows whether the kernel's device time can
be told apart from that of ordinary ops.  Exits non-zero without a TPU.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

SHAPE = (4, 741, 12, 64)        # batch, tokens (740 patches + class), heads, d


def plain(q, k, v):
    import jax
    import jax.numpy as jnp
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def relative_paths(space, root: str) -> None:
    """Source locations in the trace's stats and names, relative to the
    checkout, so the committed file names no machine's directories; the
    serialized HLO of the programs, which embeds them, is dropped (the
    reduction reads none of it)."""
    def strip(stats):
        keep = [st for st in stats
                if not (st.WhichOneof("value") == "bytes_value"
                        and root.encode() in st.bytes_value)]
        for st in keep:
            if st.WhichOneof("value") == "str_value":
                st.str_value = st.str_value.replace(root, "")
        del stats[:]
        stats.extend(keep)

    for plane in space.planes:
        for md in plane.event_metadata.values():
            md.name = md.name.replace(root, "")
            md.display_name = md.display_name.replace(root, "")
            strip(md.stats)
        for sm in plane.stat_metadata.values():
            sm.name = sm.name.replace(root, "")
        strip(plane.stats)
        for line in plane.lines:
            for ev in line.events:
                strip(ev.stats)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from harness import runtime, trace
    from repro.kernels.flash_attention import flash_attention

    runtime.accelerator(1)
    q, k, v = jax.jit(lambda key: tuple(
        jax.random.normal(kk, SHAPE, np.float32)
        for kk in jax.random.split(key, 3)))(jax.random.PRNGKey(0))

    def vit_attention(q, k, v):
        return flash_attention(q, k, v, causal=False)

    flash = jax.jit(vit_attention)
    ref = jax.jit(plain)
    y, r = flash(q, k, v), ref(q, k, v)         # compile outside the trace
    gap = float(jax.numpy.abs(y - r).max())

    spans = runtime.Spans(annotate=True)
    tmp = tempfile.mkdtemp(prefix="attention-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with spans("window"):
        # Host and device clocks of a v5e trace were seen ~1 ms apart, and
        # reduce clips the device ops to the host's window span: leave room
        # on both sides.
        time.sleep(0.02)
        with spans("attention"):
            flash(q, k, v).block_until_ready()
        with spans("plain"):
            ref(q, k, v).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    space = trace.load(trace.find_xplane(tmp))
    shutil.rmtree(tmp, ignore_errors=True)
    relative_paths(space, str(runtime.ROOT) + "/")
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_bytes(space.SerializeToString())

    red = trace.reduce(trace.load(args.out))
    print(json.dumps({"device_kind": jax.devices()[0].device_kind,
                      "max_abs_gap_to_plain": gap,
                      "bytes": Path(args.out).stat().st_size,
                      "root_left": Path(args.out).read_bytes().count(
                          str(runtime.ROOT).encode()),
                      **{k: red[k] for k in ("window_s", "busy_s",
                                             "primitive_s", "category_s",
                                             "op_s", "module_s",
                                             "module_n")}}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
