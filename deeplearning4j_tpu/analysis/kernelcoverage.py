"""Per-conv-instance Pallas kernel-coverage table.

Answers, for every ConvolutionLayer of a network conf, the question the
per-family roofline verdicts (analysis/costmodel) can only answer in
aggregate: WHICH conv instances route to the Pallas conv+BN-stats kernel
(`ops/pallas_conv_bn`), which are DECLINED by the per-instance roofline
(compute-bound — the stats epilogue saves an HBM read worth nothing
there), and which are structurally unsupported. Shapes come from
`shapeflow.propagate_types` — pure config-graph walking, no init, no
trace, no device — so the table is cheap enough for `cli perf` and the
tier-1 kernel-coverage smoke to print on any host.

The decisions are computed in PLANNING mode (`conv_decision(...,
planning=True)`): the table models the routing on the TPU the kernels
target (bf16 by default), regardless of the local backend or interpret
state. The contract the smoke enforces: every instance is decided —
covered, declined-with-verdict, or refused for a reason the chip's
compiler gave (`pallas_conv_bn.CHIP_REFUSALS`). Any other "unsupported"
means a conv shape the kernel family silently misses.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger("deeplearning4j_tpu")


def conv_instances(conf, batch: int = 128) -> List[Tuple[str, dict]]:
    """(layer_name, probe_ctx) for every 2D ConvolutionLayer in a graph
    or multilayer conf, in topological order. probe_ctx is exactly the
    keyword context `nn/layers/conv.conv_forward` passes to the "conv2d"
    helper probe (minus dtype, which the caller supplies). Layers whose
    input type cannot be propagated are skipped — they cannot exist in a
    sane conf and the caller's totals would silently lie otherwise."""
    from deeplearning4j_tpu.analysis.shapeflow import propagate_types
    from deeplearning4j_tpu.nn.conf import layers as L

    def ctx_for(layer, it) -> Optional[dict]:
        if it is None or not hasattr(it, "channels"):
            return None
        n_in = int(layer.n_in) if layer.n_in else int(it.channels)
        return dict(
            kernel=tuple(int(k) for k in layer.kernel_size),
            stride=tuple(int(s) for s in layer.stride),
            dilation=tuple(int(d) for d in layer.dilation),
            same=layer.convolution_mode == L.ConvolutionMode.SAME,
            has_bias=bool(layer.has_bias),
            activation=layer.activation or "identity",
            n_in=n_in,
            n_out=int(layer.n_out),
            x_shape=(int(batch), int(it.height), int(it.width), n_in),
            training=True,
        )

    out: List[Tuple[str, dict]] = []
    types = propagate_types(conf)
    if isinstance(types, list):  # MultiLayerConfiguration
        # layer i's INPUT is layer i-1's output (the propagated list is
        # outputs; shift by one, seeding with the conf input type)
        it = conf.input_type
        for i, layer in enumerate(conf.layers):
            pp = conf.preprocessors.get(str(i))
            if pp is not None and it is not None:
                try:
                    it = pp.output_type(it)
                except Exception:
                    it = None
            if type(layer) is L.ConvolutionLayer:
                ctx = ctx_for(layer, it)
                if ctx is not None:
                    out.append((f"layer{i}", ctx))
            it = types[i]
        return out
    for name in conf.topological_order():
        v = conf.vertices.get(name)
        layer = getattr(v, "layer", None)
        if type(layer) is not L.ConvolutionLayer:
            continue
        ins = conf.vertex_inputs.get(name, [])
        ctx = ctx_for(layer, types.get(ins[0]) if ins else None)
        if ctx is not None:
            out.append((name, ctx))
    return out


def coverage_table(conf, batch: int = 128, dtype=None) -> List[dict]:
    """One row per conv instance: the layer name, its shape, and the
    `conv_decision` routing verdict (covered / declined / unsupported
    with reason, family slug and the roofline numbers that decided it).
    dtype defaults to bf16 — the precision the TPU rounds run."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_conv_bn import conv_decision

    if dtype is None:
        dtype = jnp.bfloat16
    rows = []
    for name, ctx in conv_instances(conf, batch=batch):
        d = conv_decision(dtype=dtype, planning=True, **ctx)
        row = {
            "layer": name,
            "kernel": list(ctx["kernel"]),
            "stride": list(ctx["stride"]),
            "x_shape": list(ctx["x_shape"]),
            "n_out": ctx["n_out"],
            "status": d["status"],
            "reason": d["reason"],
            "family": d["family"],
        }
        if d["roofline"] is not None:
            row["intensity"] = d["roofline"]["intensity"]
            row["ridge"] = d["roofline"]["ridge_intensity"]
        rows.append(row)
    return rows


def coverage_summary(rows: List[dict]) -> Dict[str, int]:
    counts = {"total": len(rows), "covered": 0, "declined": 0,
              "unsupported": 0}
    for r in rows:
        counts[r["status"]] = counts.get(r["status"], 0) + 1
    return counts


def undecided(rows: List[dict]) -> List[dict]:
    """Rows that are unsupported for another reason than the chip
    compiler's verdict on the shape — holes in the kernel family."""
    from deeplearning4j_tpu.ops.pallas_conv_bn import CHIP_REFUSALS

    return [r for r in rows if r["status"] == "unsupported"
            and r["reason"] not in CHIP_REFUSALS]


def format_table(rows: List[dict]) -> str:
    s = coverage_summary(rows)
    lines = [f"Pallas conv kernel coverage: {s['total']} conv instances — "
             f"{s['covered']} covered, {s['declined']} declined "
             f"(roofline), {s['unsupported']} unsupported"]
    lines.append(f"  {'layer':<14} {'kernel':>6} {'stride':>6} "
                 f"{'input (NHWC)':>20} {'n_out':>5} {'FLOP/B':>8}  "
                 f"decision")
    for r in rows:
        k = "x".join(str(v) for v in r["kernel"])
        st = "x".join(str(v) for v in r["stride"])
        shape = "x".join(str(v) for v in r["x_shape"])
        inten = f"{r['intensity']:.0f}" if "intensity" in r else "-"
        verdict = r["status"]
        if r["status"] != "covered":
            verdict += f" ({r['reason']})"
        lines.append(f"  {r['layer']:<14} {k:>6} {st:>6} {shape:>20} "
                     f"{r['n_out']:>5} {inten:>8}  {verdict}")
    return "\n".join(lines)


def main(argv=None) -> int:
    """Kernel-coverage smoke (scripts/t1.sh `T1 KERNEL COVERAGE:`):
    assert every conv instance of the preset resolves to covered,
    declined-with-verdict or refused-by-the-chip-compiler — a
    silently-unsupported shape fails the gate, because that is a
    kernel-family hole nobody decided on."""
    import argparse

    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("--preset", default="resnet50", choices=["resnet50"])
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--table", action="store_true",
                   help="print the full per-instance table")
    args = p.parse_args(argv)
    # operator surface: announce through the package logger (library
    # code never prints — lint CC006), same as the server mains
    from deeplearning4j_tpu import configure_logging

    if all(isinstance(h, logging.NullHandler) for h in logger.handlers):
        configure_logging()
    from deeplearning4j_tpu.models.resnet import resnet50_conf

    conf = resnet50_conf()
    rows = coverage_table(conf, batch=args.batch)
    if args.table:
        logger.info("%s", format_table(rows))
    s = coverage_summary(rows)
    holes = undecided(rows)
    ok = not holes and s["total"] > 0
    logger.info(
        "kernel coverage %s (batch %d, bf16): %d conv instances — "
        "%d covered, %d declined (roofline), %d refused by the chip "
        "compiler, %d undecided -> %s",
        args.preset, args.batch, s["total"], s["covered"], s["declined"],
        s["unsupported"] - len(holes), len(holes), "ok" if ok else "FAIL")
    for r in holes:
        logger.error("UNSUPPORTED: %s kernel=%s stride=%s reason=%s",
                     r["layer"], r["kernel"], r["stride"], r["reason"])
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
