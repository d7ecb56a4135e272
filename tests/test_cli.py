"""End-to-end CLI tests on tiny synthetic data (SURVEY.md §4f): one real
train_vae run (loss decreases, checkpoint restorable), kill/resume, the
VAE->DALLE->gen_dalle pipeline text-in -> PNG-out, and the mix_vae demo."""

import json
import os

import numpy as np
import pytest

from dalle_pytorch_tpu import checkpoint as ckpt

IMG = 16          # tiny images: 2 conv layers -> 4x4 = 16 image tokens


@pytest.fixture(scope="module")
def workdir(tmp_path_factory):
    """Synthetic dataset: 8 images + captions, shared dirs for all tests."""
    from PIL import Image
    root = tmp_path_factory.mktemp("cli")
    img_dir = root / "imagedata" / "0"
    img_dir.mkdir(parents=True)
    rng = np.random.default_rng(0)
    names = []
    for i in range(8):
        arr = np.zeros((IMG, IMG, 3), np.uint8)
        # structured content so the VAE has something to learn
        arr[:, :, i % 3] = 255
        arr[i:i + 6, i:i + 6] = rng.integers(0, 255, (6, 6, 3))
        name = f"img{i}.png"
        Image.fromarray(arr).save(img_dir / name)
        names.append(name)
    colors = ["red", "blue", "green", "gray"]
    (root / "only.txt").write_text(
        "".join(f"a {colors[i % 4]} square\n" for i in range(8)))
    (root / "pairs.txt").write_text(
        "".join(f"{n} : a {colors[i % 4]} square\n"
                for i, n in enumerate(names)))
    (root / "models").mkdir()
    (root / "results").mkdir()
    return root


def vae_args(root, extra=()):
    return [
        "--dataPath", str(root / "imagedata"),
        "--imageSize", str(IMG), "--batchSize", "4",
        "--num_layers", "2", "--num_tokens", "24", "--codebook_dim", "16",
        "--hidden_dim", "8", "--lr", "3e-3",
        "--models_dir", str(root / "models"),
        "--results_dir", str(root / "results"),
        "--metrics", str(root / "metrics.jsonl"),
        "--log_interval", "1", "--dp", "1",
    ] + list(extra)


@pytest.mark.slow
class TestTrainVAE:
    def test_two_epochs_decreasing_loss_and_artifacts(self, workdir):
        from dalle_pytorch_tpu.cli.train_vae import main
        # --guard_transfers: the CI train smoke runs the real step body
        # under analysis.guards.no_transfers — an implicit host<->device
        # transfer creeping into the hot path fails the test, naming the
        # offending call (ROADMAP's no_transfers-around-train-step item)
        main(vae_args(workdir, ["--n_epochs", "2", "--tempsched",
                                "--guard_transfers"]))

        # loss decreased epoch 0 -> 1
        losses = {}
        with open(workdir / "metrics.jsonl") as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") == "checkpoint":
                    losses[rec["epoch"]] = rec["avg_loss"]
        assert losses[1] < losses[0]

        # recon grid written per epoch
        assert (workdir / "results" / "vae_epoch_0.png").exists()
        assert (workdir / "results" / "vae_epoch_1.png").exists()

        # checkpoint restorable with config + schedule state
        path, epoch = ckpt.latest(str(workdir / "models"), "vae")
        assert epoch == 1
        params, manifest = ckpt.restore_params(path)
        assert manifest["kind"] == "vae"
        assert manifest["meta"]["temperature"] < 0.9   # tempsched ran
        cfg = ckpt.vae_config_from_manifest(manifest)
        assert cfg.image_size == IMG and cfg.num_tokens == 24

    def test_resume_from_checkpoint(self, workdir):
        """Kill/resume: epoch numbering continues, opt state restores
        (reference --loadVAE/--start_epoch, trainVAE.py:20-21,52-54)."""
        from dalle_pytorch_tpu.cli.train_vae import main
        main(vae_args(workdir, ["--n_epochs", "1", "--loadVAE", "vae",
                                "--start_epoch", "2"]))
        path, epoch = ckpt.latest(str(workdir / "models"), "vae")
        assert epoch == 2
        assert ckpt.load_manifest(path)["meta"]["epoch"] == 2


def require_ckpt(workdir, name, epoch):
    """The CLI tests build on each other's checkpoints through the
    module-scoped workdir (train_vae -> train_dalle -> gen/mix/clip).
    Running a later class alone skips with a pointer instead of a
    confusing FileNotFoundError."""
    if ckpt.latest(str(workdir / "models"), name) is None:
        pytest.skip(f"needs the {name!r} checkpoint from the earlier CLI "
                    "tests in this module — run the whole file")


@pytest.mark.slow
class TestTrainDALLE:
    def test_train_and_sample(self, workdir):
        require_ckpt(workdir, "vae", 2)
        from dalle_pytorch_tpu.cli.train_dalle import main
        main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "4",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--vaename", "vae", "--vae_epoch", "2",
            "--name", "toy", "--n_epochs", "1",
            "--dim", "16", "--depth", "2", "--heads", "2",
            "--dim_head", "8", "--num_text_tokens", "50",
            "--text_seq_len", "8", "--attn_dropout", "0",
            "--ff_dropout", "0", "--lr", "1e-3",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--dp", "1", "--sample_every", "1",
            "--guard_transfers",
        ])
        # checkpoint + vocab + sample grid exist
        path, epoch = ckpt.latest(str(workdir / "models"), "toy_dalle")
        assert epoch == 0
        manifest = ckpt.load_manifest(path)
        assert manifest["kind"] == "dalle"
        assert manifest["meta"]["vae_checkpoint"].endswith("vae-2")
        assert (workdir / "models" / "toy-vocab.json").exists()
        assert (workdir / "results" / "toy_dalle_epoch_0.png").exists()

        # codebook tie: image_emb was seeded from the VAE codebook and
        # trained; config round-trips
        cfg = ckpt.dalle_config_from_manifest(manifest)
        assert cfg.dim == 16 and cfg.vae.num_tokens == 24

    def test_gen_dalle_text_to_png(self, workdir):
        require_ckpt(workdir, "toy_dalle", 0)
        from dalle_pytorch_tpu.cli.gen_dalle import main
        main([
            "a red square",
            "--name", "toy", "--dalle_epoch", "0",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--num_images", "2",
        ])
        outs = [f for f in os.listdir(workdir / "results")
                if f.startswith("gendalletoy_epoch_0-")]
        assert outs, "gen_dalle wrote no PNG"

    def test_ema_train_and_sample(self, workdir):
        """--ema_decay writes EMA weights with the checkpoint and
        gen_dalle --use_ema samples from them (beyond-reference)."""
        require_ckpt(workdir, "vae", 2)
        from dalle_pytorch_tpu.cli.gen_dalle import main as gen_main
        from dalle_pytorch_tpu.cli.train_dalle import main as train_main
        train_main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "4",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--vaename", "vae", "--vae_epoch", "2",
            "--name", "toy_ema", "--n_epochs", "1",
            "--dim", "16", "--depth", "2", "--heads", "2",
            "--dim_head", "8", "--num_text_tokens", "50",
            "--text_seq_len", "8", "--attn_dropout", "0",
            "--ff_dropout", "0", "--lr", "1e-3",
            "--ema_decay", "0.99",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--dp", "1", "--sample_every", "0",
        ])
        path, _ = ckpt.latest(str(workdir / "models"), "toy_ema_dalle")
        ema = ckpt.restore_ema(path)
        assert ema is not None
        import jax.numpy as jnp
        assert all(leaf.dtype == jnp.float32
                   for leaf in __import__("jax").tree.leaves(ema))
        before = set(os.listdir(workdir / "results"))
        gen_main([
            "a red square",
            "--name", "toy_ema", "--dalle_epoch", "0", "--use_ema",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
        ])
        new = set(os.listdir(workdir / "results")) - before
        assert any(f.startswith("gendalletoy_ema_epoch_0-") for f in new)

    def test_caption_drop_and_guided_gen(self, workdir):
        """--caption_drop trains through null captions; gen_dalle
        --guidance samples with classifier-free guidance."""
        require_ckpt(workdir, "vae", 2)
        from dalle_pytorch_tpu.cli.gen_dalle import main as gen_main
        from dalle_pytorch_tpu.cli.train_dalle import main as train_main
        train_main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "4",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--vaename", "vae", "--vae_epoch", "2",
            "--name", "toy_cfg", "--n_epochs", "1",
            "--dim", "16", "--depth", "2", "--heads", "2",
            "--dim_head", "8", "--num_text_tokens", "50",
            "--text_seq_len", "8", "--attn_dropout", "0",
            "--ff_dropout", "0", "--lr", "1e-3",
            "--caption_drop", "0.5",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--dp", "1", "--sample_every", "0",
        ])
        before = set(os.listdir(workdir / "results"))
        gen_main([
            "a red square",
            "--name", "toy_cfg", "--dalle_epoch", "0",
            "--guidance", "3.0",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
        ])
        new = set(os.listdir(workdir / "results")) - before
        assert any(f.startswith("gendalletoy_cfg_epoch_0-") for f in new)

    def test_caption_drop_rejected_under_sp(self, workdir):
        from dalle_pytorch_tpu.cli.train_dalle import main as train_main
        with pytest.raises(SystemExit, match="dense path"):
            train_main([
                "--dataPath", str(workdir / "imagedata"),
                "--captions_only", str(workdir / "only.txt"),
                "--captions", str(workdir / "pairs.txt"),
                "--vaename", "vae", "--vae_epoch", "2",
                "--caption_drop", "0.1", "--sp", "2", "--dp", "1",
                "--models_dir", str(workdir / "models"),
                "--results_dir", str(workdir / "results"),
            ])

    @pytest.mark.parametrize("mode", ["int8", "int8_kv"])
    def test_gen_dalle_quantized(self, workdir, mode):
        """--quantize int8 runs the same sampler on int8 linears
        (ops/quant.py); int8_kv additionally stores the KV cache int8
        (ops/decode.py). Both still write a grid."""
        require_ckpt(workdir, "toy_dalle", 0)
        from dalle_pytorch_tpu.cli.gen_dalle import main
        # the grid's name carries whole seconds: with a warm compile
        # cache both modes finish inside one, so clear the slate rather
        # than diff the listing
        for f in os.listdir(workdir / "results"):
            if f.startswith("gendalletoy_epoch_0-"):
                os.remove(workdir / "results" / f)
        main([
            "a red square",
            "--name", "toy", "--dalle_epoch", "0",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--quantize", mode,
        ])
        assert any(f.startswith("gendalletoy_epoch_0-")
                   for f in os.listdir(workdir / "results")), \
            "quantized gen_dalle wrote no PNG"

    def test_gen_dalle_clip_rerank(self, workdir):
        require_ckpt(workdir, "toy_dalle", 0)
        """--clip_name reranks the jitted sampler's output (reference
        dalle_pytorch.py:354-356); scores print best-first and a grid is
        still written."""
        import jax
        import jax.numpy as jnp
        from dalle_pytorch_tpu.models import clip as C
        ccfg = C.CLIPConfig(dim_text=16, dim_image=16, dim_latent=8,
                            num_text_tokens=50, text_seq_len=8,
                            text_enc_depth=1, visual_enc_depth=1,
                            text_heads=2, visual_heads=2,
                            visual_image_size=IMG, visual_patch_size=8,
                            sparse_attn=False)
        cparams = C.clip_init(jax.random.PRNGKey(3), ccfg)
        ckpt.save(ckpt.ckpt_path(str(workdir / "models"), "clip", 0),
                  cparams, step=0, config=ccfg, kind="clip")

        from dalle_pytorch_tpu.cli.gen_dalle import main
        scores_path = workdir / "scores.jsonl"
        main([
            "a red square",
            "--name", "toy", "--dalle_epoch", "0",
            "--clip_name", "clip", "--clip_epoch", "0",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--num_images", "2", "--guidance", "0",
            "--scores_json", str(scores_path),
        ])
        outs = [f for f in os.listdir(workdir / "results")
                if f.startswith("gendalletoy_epoch_0-")]
        assert outs
        # --scores_json appended a machine-readable adherence record
        import json
        rec = json.loads(scores_path.read_text().splitlines()[-1])
        assert rec["caption"] == "a red square"
        assert rec["guidance"] == 0.0
        assert len(rec["scores"]) == 2
        assert rec["scores"] == sorted(rec["scores"], reverse=True)

    def test_gen_dalle_oov_raises(self, workdir):
        from dalle_pytorch_tpu.cli.gen_dalle import main
        with pytest.raises(KeyError):
            main(["a purple hexagon", "--name", "toy", "--dalle_epoch", "0",
                  "--models_dir", str(workdir / "models"),
                  "--results_dir", str(workdir / "results")])


@pytest.mark.slow
class TestMixVAE:
    def test_mix_grids(self, workdir):
        from dalle_pytorch_tpu.cli.mix_vae import main
        out_dir = workdir / "mixed"
        main([
            "--vaename", "vae", "--load_epoch", "2",
            "--models_dir", str(workdir / "models"),
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "4",
            "--out_dir", str(out_dir), "--max_batches", "1",
        ])
        assert (out_dir / "mixed_epoch_2_0.png").exists()


class TestResolveResume:
    def test_bare_name_uses_latest(self, tmp_path):
        from dalle_pytorch_tpu.cli.common import resolve_resume
        params = {"w": np.zeros((2,))}
        for e in (0, 4):
            ckpt.save(ckpt.ckpt_path(str(tmp_path), "vae", e), params,
                      step=e)
        path, start = resolve_resume("vae", str(tmp_path), 0)
        assert path.endswith("vae-4") and start == 5

    def test_explicit_epoch(self, tmp_path):
        from dalle_pytorch_tpu.cli.common import resolve_resume
        path, start = resolve_resume("vae", str(tmp_path), 3)
        assert path.endswith("vae-2") and start == 3

    def test_missing_name_raises(self, tmp_path):
        from dalle_pytorch_tpu.cli.common import resolve_resume
        with pytest.raises(FileNotFoundError):
            resolve_resume("ghost", str(tmp_path), 0)


@pytest.mark.slow
class TestParamDtype:
    def test_bf16_vae_trains_and_checkpoints(self, workdir, tmp_path):
        import jax
        import jax.numpy as jnp
        from dalle_pytorch_tpu.cli.train_vae import main
        main(vae_args(workdir, ["--n_epochs", "1", "--param_dtype",
                                "bfloat16", "--name", "vae16",
                                "--models_dir", str(tmp_path)]))
        path, _ = ckpt.latest(str(tmp_path), "vae16")
        params, _ = ckpt.restore_params(path)
        leaves = jax.tree.leaves(params)
        assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)


class TestLRScheduleMath:
    """make_optimizer's schedule values, independent of any CLI run."""

    @staticmethod
    def _args(**kw):
        import argparse
        base = dict(lr=1e-3, lr_schedule="cosine", warmup_steps=10,
                    decay_steps=0, lr_end_ratio=0.1, n_epochs=4)
        base.update(kw)
        return argparse.Namespace(**base)

    @staticmethod
    def _lr_at(opt, step):
        """Effective LR at ``step`` read off a single-param update."""
        import jax.numpy as jnp
        params = {"w": jnp.zeros(())}
        state = opt.init(params)
        # advance the optimizer count to `step`
        for _ in range(step):
            _, state = opt.update({"w": jnp.ones(())}, state, params)
        upd, _ = opt.update({"w": jnp.ones(())}, state, params)
        # adam update of a constant unit gradient = -lr (bias-corrected
        # m/sqrt(v) == 1 for every step with a constant gradient)
        return float(-upd["w"])

    def test_warmup_reaches_peak_and_decays_to_floor(self):
        from dalle_pytorch_tpu.cli.common import make_optimizer
        args = self._args()
        opt = make_optimizer(args, steps_per_epoch=10, start_epoch=0)
        lr_peak = self._lr_at(opt, 10)        # end of warmup
        lr_mid = self._lr_at(opt, 25)
        lr_end = self._lr_at(opt, 40)         # horizon = 4 * 10
        assert lr_peak == pytest.approx(1e-3, rel=0.05)
        assert 1e-4 < lr_mid < 1e-3
        assert lr_end == pytest.approx(1e-4, rel=0.1)   # lr * end_ratio

    def test_resume_extends_horizon(self):
        """start_epoch shifts the cosine horizon so a resumed run keeps
        decaying instead of sitting at the floor from step 0."""
        from dalle_pytorch_tpu.cli.common import make_optimizer
        args = self._args(warmup_steps=0)
        fresh = make_optimizer(args, steps_per_epoch=10, start_epoch=0)
        resumed = make_optimizer(args, steps_per_epoch=10, start_epoch=4)
        # at optimizer step 40: the fresh horizon (40) is exhausted, the
        # resumed horizon (80) is mid-decay
        assert self._lr_at(fresh, 40) == pytest.approx(1e-4, rel=0.1)
        assert self._lr_at(resumed, 40) > 2e-4

    def test_constant_with_warmup_holds_peak(self):
        from dalle_pytorch_tpu.cli.common import make_optimizer
        args = self._args(lr_schedule="constant", warmup_steps=5)
        opt = make_optimizer(args, steps_per_epoch=10, start_epoch=0)
        assert self._lr_at(opt, 2) < 1e-3
        assert self._lr_at(opt, 50) == pytest.approx(1e-3, rel=0.02)

    def test_clip_grad_norm_chains_and_clips(self):
        """--clip_grad_norm caps the gradient BEFORE adam's moments.
        Adam's first step is sign-normalized (update ~ g/|g| for any
        magnitude), so a one-step comparison cannot see the clip; the
        second moment CAN — an unclipped 5e6-norm gradient poisons v and
        collapses the next update toward zero, a clipped one does not."""
        import jax.numpy as jnp

        from dalle_pytorch_tpu.cli.common import make_optimizer

        def two_step_second_update(opt):
            params = {"w": jnp.zeros((2,))}
            state = opt.init(params)
            u1, state = opt.update({"w": jnp.array([3e6, 4e6])}, state,
                                   params)
            u2, _ = opt.update({"w": jnp.array([0.6, 0.8])}, state, params)
            return u2["w"]

        clipped = make_optimizer(self._args(lr_schedule="constant",
                                            warmup_steps=0,
                                            clip_grad_norm=1.0))
        plain = make_optimizer(self._args(lr_schedule="constant",
                                          warmup_steps=0,
                                          clip_grad_norm=0.0))
        u2_clip = two_step_second_update(clipped)
        u2_plain = two_step_second_update(plain)
        # with the clip, step 2 sees two same-scale gradients -> full
        # lr-sized update; without it, the 5e6-norm outlier dominates both
        # moments and drags the next update to ~0.67*lr (adam's bias
        # correction cancels most but not all of the poisoning). The gap
        # exists ONLY when the clip is chained.
        assert float(jnp.abs(u2_clip).min()) > 0.98e-3
        assert float(jnp.abs(u2_plain).max()) < 0.75e-3

    def test_resolve_schedule_snapshot_wins_on_resume(self):
        """--auto_resume reconstructs the ORIGINAL cosine horizon from the
        checkpoint's persisted lr_schedule meta: a restart with the
        remaining epoch count (n_epochs=1) must NOT shrink the decay to
        the remaining run (ROADMAP open item)."""
        from dalle_pytorch_tpu.cli.common import resolve_schedule
        # original run: 4 epochs x 10 steps -> horizon 30 after warmup
        orig = resolve_schedule(self._args(), steps_per_epoch=10,
                                start_epoch=0)
        assert orig["decay_steps"] == 30
        assert orig["epochs_total"] == 4
        # restart passes only the REMAINING epochs; the snapshot rides the
        # checkpoint meta and keeps the original horizon + total
        resumed = resolve_schedule(self._args(n_epochs=1),
                                   steps_per_epoch=10, start_epoch=3,
                                   resume_meta={"lr_schedule": orig})
        assert resumed["decay_steps"] == 30
        assert resumed["epochs_total"] == 4
        # an explicit --decay_steps still wins over the snapshot
        forced = resolve_schedule(self._args(n_epochs=1, decay_steps=77),
                                  steps_per_epoch=10, start_epoch=3,
                                  resume_meta={"lr_schedule": orig})
        assert forced["decay_steps"] == 77

    def test_make_optimizer_uses_schedule_snapshot(self):
        """An original run pinned --decay_steps 120; the restart does NOT
        re-pass it. With the checkpoint's snapshot the optimizer keeps
        decaying over the original 120-step horizon; without it, the
        recomputed default horizon (40) has already bottomed out."""
        from dalle_pytorch_tpu.cli.common import (make_optimizer,
                                                  resolve_schedule)
        orig = resolve_schedule(self._args(warmup_steps=0,
                                           decay_steps=120),
                                steps_per_epoch=10, start_epoch=0)
        assert orig["decay_steps"] == 120
        restart_args = self._args(warmup_steps=0, n_epochs=1)   # no flag
        snap = resolve_schedule(restart_args, steps_per_epoch=10,
                                start_epoch=3,
                                resume_meta={"lr_schedule": orig})
        with_snap = make_optimizer(restart_args, schedule=snap)
        without = make_optimizer(restart_args, steps_per_epoch=10,
                                 start_epoch=3)
        assert self._lr_at(without, 50) == pytest.approx(1e-4, rel=0.1)
        assert self._lr_at(with_snap, 50) > 2e-4

    def test_resume_with_toggled_clip_fails_clearly(self):
        """Toggling --clip_grad_norm on resume changes the opt-state tree;
        restore must say which flags to check, not raise a raw flax
        KeyError (checkpoint.restore_train guard)."""
        import jax.numpy as jnp

        from dalle_pytorch_tpu import checkpoint as ckpt_mod
        from dalle_pytorch_tpu.cli.common import make_optimizer
        params = {"w": jnp.zeros((2,))}
        plain = make_optimizer(self._args(lr_schedule="constant",
                                          warmup_steps=0,
                                          clip_grad_norm=0.0))
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            path = ckpt_mod.save(f"{d}/ck-0", params,
                                 opt_state=plain.init(params),
                                 config={}, meta={})
            clipped = make_optimizer(self._args(lr_schedule="constant",
                                                warmup_steps=0,
                                                clip_grad_norm=1.0))
            with pytest.raises(ValueError, match="clip_grad_norm"):
                ckpt_mod.restore_train(path, clipped)


@pytest.mark.slow
class TestLRSchedule:
    def test_cosine_warmup_trains(self, workdir, tmp_path):
        """--lr_schedule cosine --warmup_steps: beyond-reference schedule
        (fixed-LR Adam only, reference trainVAE.py:69) trains and
        checkpoints; the horizon defaults to the requested run length."""
        from dalle_pytorch_tpu.cli.train_vae import main
        main(vae_args(workdir, [
            "--n_epochs", "1", "--name", "cosvae",
            "--lr_schedule", "cosine", "--warmup_steps", "2",
            "--models_dir", str(tmp_path),
        ]))
        assert ckpt.latest(str(tmp_path), "cosvae")[1] == 0

    def test_schedule_resumes_from_opt_count(self, workdir, tmp_path):
        """Resume continues the schedule: the restored opt state carries
        the step count the schedule rides."""
        from dalle_pytorch_tpu.cli.train_vae import main
        sched = ["--lr_schedule", "cosine", "--warmup_steps", "2",
                 "--models_dir", str(tmp_path)]
        main(vae_args(workdir, ["--n_epochs", "1", "--name", "cosres"]
                      + sched))
        main(vae_args(workdir, ["--n_epochs", "1", "--name", "cosres",
                                "--loadVAE", "cosres"] + sched))
        assert ckpt.latest(str(tmp_path), "cosres")[1] == 1


@pytest.mark.slow
class TestTrainDALLESequenceParallel:
    def test_sp_train_runs_and_checkpoints(self, workdir, tmp_path):
        require_ckpt(workdir, "vae", 2)
        """--sp 4 on the 8-device CPU mesh: dp=2 x sp=4, ring attention in
        the stack, one epoch trains and checkpoints."""
        from dalle_pytorch_tpu.cli.train_dalle import main
        main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "4",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--vaename", "vae", "--vae_epoch", "2",
            "--name", "sptoy", "--n_epochs", "1",
            "--dim", "16", "--depth", "2", "--heads", "4",
            "--dim_head", "4", "--num_text_tokens", "50",
            "--text_seq_len", "8", "--attn_dropout", "0",
            "--ff_dropout", "0", "--lr", "1e-3", "--sp", "4",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--sample_every", "100",
        ])
        path, epoch = ckpt.latest(str(workdir / "models"), "sptoy_dalle")
        assert epoch == 0

    def test_sp_trains_with_dropout(self, workdir):
        """--sp with the flagship nonzero dropout (r3 item 7): accepted and
        trains — positional dropout keys make it SPMD-safe."""
        require_ckpt(workdir, "vae", 2)
        from dalle_pytorch_tpu.cli.train_dalle import main
        main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "4",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--vaename", "vae", "--vae_epoch", "2",
            "--name", "spdrop", "--n_epochs", "1",
            "--dim", "16", "--depth", "2", "--heads", "4",
            "--dim_head", "4", "--num_text_tokens", "50",
            "--text_seq_len", "8", "--attn_dropout", "0.1",
            "--ff_dropout", "0.1", "--lr", "1e-3", "--sp", "4",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--sample_every", "100",
        ])
        path, epoch = ckpt.latest(str(workdir / "models"), "spdrop_dalle")
        assert epoch == 0

    def test_sp_trains_with_remat_full(self, workdir):
        """--sp 4 --remat full (VERDICT r4 item 7): sequence sharding and
        activation thrift compose in one program — the long-context
        training recipe trains and checkpoints through the CLI."""
        require_ckpt(workdir, "vae", 2)
        from dalle_pytorch_tpu.cli.train_dalle import main
        main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "4",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--vaename", "vae", "--vae_epoch", "2",
            "--name", "spremat", "--n_epochs", "1",
            "--dim", "16", "--depth", "2", "--heads", "4",
            "--dim_head", "4", "--num_text_tokens", "50",
            "--text_seq_len", "8", "--attn_dropout", "0",
            "--ff_dropout", "0", "--lr", "1e-3", "--sp", "4",
            "--remat", "full",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--sample_every", "100",
        ])
        path, epoch = ckpt.latest(str(workdir / "models"), "spremat_dalle")
        assert epoch == 0


class TestTrainDALLEMoE:
    def test_moe_train_runs_and_checkpoints(self, workdir):
        """--moe_experts 4: the MoE FF trains end-to-end through the CLI
        (aux loss in the objective) and checkpoints."""
        require_ckpt(workdir, "vae", 2)
        from dalle_pytorch_tpu.cli.train_dalle import main
        main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "8",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--vaename", "vae", "--vae_epoch", "2",
            "--name", "moetoy", "--n_epochs", "1",
            "--dim", "16", "--depth", "2", "--heads", "4",
            "--dim_head", "4", "--num_text_tokens", "50",
            "--text_seq_len", "8", "--moe_experts", "4",
            "--lr", "1e-3", "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--sample_every", "100",
        ])
        path, epoch = ckpt.latest(str(workdir / "models"), "moetoy_dalle")
        assert epoch == 0


class TestTrainDALLERemat:
    def test_remat_full_trains_and_checkpoints(self, workdir):
        """--remat full: the rematerialized layer body trains end-to-end
        through the CLI (the batch-unlocking lever)."""
        require_ckpt(workdir, "vae", 2)
        from dalle_pytorch_tpu.cli.train_dalle import main
        main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "8",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--vaename", "vae", "--vae_epoch", "2",
            "--name", "remattoy", "--n_epochs", "1",
            "--dim", "16", "--depth", "2", "--heads", "4",
            "--dim_head", "4", "--num_text_tokens", "50",
            "--text_seq_len", "8", "--remat", "full",
            "--lr", "1e-3", "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--sample_every", "100",
        ])
        path, epoch = ckpt.latest(str(workdir / "models"), "remattoy_dalle")
        assert epoch == 0


class TestTrainDALLEPipelineParallel:
    def test_pp_train_runs_and_checkpoints(self, workdir):
        """--pp 4 on the 8-device CPU mesh: dp=2 x pp=4, one layer per
        stage with the stack stage-sharded, one epoch trains and
        checkpoints (r3 item 6: pp is trainable, mirroring --sp)."""
        require_ckpt(workdir, "vae", 2)
        from dalle_pytorch_tpu.cli.train_dalle import main
        main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "8",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--vaename", "vae", "--vae_epoch", "2",
            "--name", "pptoy", "--n_epochs", "1",
            "--dim", "16", "--depth", "4", "--heads", "4",
            "--dim_head", "4", "--num_text_tokens", "50",
            "--text_seq_len", "8", "--attn_dropout", "0.1",
            "--ff_dropout", "0.1", "--lr", "1e-3", "--pp", "4",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--sample_every", "100",
        ])
        path, epoch = ckpt.latest(str(workdir / "models"), "pptoy_dalle")
        assert epoch == 0


@pytest.mark.slow
class TestTrainCLIP:
    def test_train_and_rerank_pipeline(self, workdir):
        require_ckpt(workdir, "toy_dalle", 0)
        """train_clip one epoch on the synthetic pairs, then gen_dalle
        reranks with the TRAINED checkpoint — the full reranker pipeline
        (reference README.md:119-126) as CLIs."""
        from dalle_pytorch_tpu.cli.train_clip import main
        main([
            "--dataPath", str(workdir / "imagedata"),
            "--imageSize", str(IMG), "--batchSize", "4",
            "--captions_only", str(workdir / "only.txt"),
            "--captions", str(workdir / "pairs.txt"),
            "--name", "clipcli", "--n_epochs", "1",
            "--dim_text", "16", "--dim_image", "16", "--dim_latent", "8",
            "--num_text_tokens", "50", "--text_seq_len", "8",
            "--text_enc_depth", "1", "--visual_enc_depth", "1",
            "--text_heads", "2", "--visual_heads", "2",
            "--visual_patch_size", "8", "--dense", "--lr", "1e-3",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--log_interval", "1", "--dp", "1", "--guard_transfers",
        ])
        path, epoch = ckpt.latest(str(workdir / "models"), "clipcli")
        assert epoch == 0
        manifest = ckpt.load_manifest(path)
        assert manifest["kind"] == "clip"

        from dalle_pytorch_tpu.cli.gen_dalle import main as gen_main
        gen_main([
            "a red square",
            "--name", "toy", "--dalle_epoch", "0",
            "--clip_name", "clipcli", "--clip_epoch", "0",
            "--models_dir", str(workdir / "models"),
            "--results_dir", str(workdir / "results"),
            "--num_images", "2",
        ])
