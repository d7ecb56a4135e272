"""Render the demo's training loss curves (docs/demo/*.jsonl) to one PNG.

The JSONLs are appended across resumed runs with a
per-invocation step counter, so curves are aggregated per EPOCH, and
when an epoch appears in more than one invocation (a run died
mid-epoch and the resume retrained it) only the NEWEST invocation's
records count — stale partial-epoch records from the aborted attempt
are dropped. VAE and DALLE losses live on different scales, so they get
two panels (never a dual axis).

Run: python scripts/plot_demo.py [--dir docs/demo]
"""

import argparse
import json
import os


def epoch_series(path):
    """epoch -> mean loss over that epoch's records from the newest run.

    A run boundary is a step-counter reset (each invocation counts steps
    from 0, monotonically); per epoch, only records from the latest run
    that touched it are kept, so an aborted attempt's partial records
    don't blend into the retrained epoch's point."""
    if not os.path.exists(path):
        return [], []
    by_epoch = {}                          # epoch -> run -> [losses]
    run, prev_step = 0, None
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if not ("loss" in rec and "epoch" in rec and "step" in rec):
                continue
            if prev_step is not None and rec["step"] <= prev_step:
                run += 1
            prev_step = rec["step"]
            by_epoch.setdefault(rec["epoch"], {}).setdefault(
                run, []).append(rec["loss"])
    epochs = sorted(by_epoch)
    means = []
    for e in epochs:
        losses = by_epoch[e][max(by_epoch[e])]
        means.append(sum(losses) / len(losses))
    return epochs, means


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="docs/demo")
    ap.add_argument("--out", default=None,
                    help="default: <dir>/loss_curves.png")
    args = ap.parse_args()

    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except ImportError:
        print("matplotlib not installed (it is not a package dependency); "
              "pip install matplotlib to render loss curves")
        return

    panels = []
    for fname, title in (("vae_loss.jsonl", "DiscreteVAE recon loss"),
                         ("dalle_loss.jsonl", "DALLE token CE loss")):
        ep, loss = epoch_series(os.path.join(args.dir, fname))
        if ep:
            panels.append((title, ep, loss))
    if not panels:
        print("no loss JSONLs found; nothing to plot")
        return

    ink, muted, series = "#0b0b0b", "#52514e", "#2a78d6"
    fig, axes = plt.subplots(1, len(panels), figsize=(5.2 * len(panels), 3.4),
                             facecolor="#fcfcfb")
    if len(panels) == 1:
        axes = [axes]
    for ax, (title, ep, loss) in zip(axes, panels):
        ax.set_facecolor("#fcfcfb")
        ax.plot(ep, loss, color=series, linewidth=2)
        ax.set_title(title, color=ink, fontsize=11, loc="left")
        ax.set_xlabel("epoch", color=muted, fontsize=9)
        ax.set_ylabel("loss", color=muted, fontsize=9)
        ax.tick_params(colors=muted, labelsize=8)
        ax.grid(True, color="#e8e7e2", linewidth=0.6)
        for side in ("top", "right"):
            ax.spines[side].set_visible(False)
        for side in ("left", "bottom"):
            ax.spines[side].set_color("#e8e7e2")
        # direct label on the final point (selective, not every point)
        ax.annotate(f"{loss[-1]:.3f}", (ep[-1], loss[-1]),
                    textcoords="offset points", xytext=(4, 4),
                    color=ink, fontsize=8)
    fig.tight_layout()
    out = args.out or os.path.join(args.dir, "loss_curves.png")
    fig.savefig(out, dpi=150)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
