"""End-to-end training driver: data pipeline -> sharded train step ->
checkpoint/resume, with visibly decreasing loss on a learnable stream.

    PYTHONPATH=src python examples/train_lm.py                  # ci preset
    PYTHONPATH=src python examples/train_lm.py --preset full    # ~100M model
"""
import argparse
import shutil

from repro.configs.base import ArchConfig
from repro.data.tokens import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.optim.adamw import OptConfig
from repro.train.loop import LoopConfig, train
from repro.train.train_step import TrainConfig

PRESETS = {
    # runs in minutes on one CPU core
    "ci": dict(cfg=ArchConfig(name="ci-28m", family="dense", n_layers=4,
                              d_model=256, n_heads=4, n_kv_heads=2,
                              d_ff=1024, vocab=8192, head_dim=64),
               batch=8, seq=128, steps=120),
    # ~100M params; a few hundred steps (sized for a real machine)
    "full": dict(cfg=ArchConfig(name="lm-100m", family="dense", n_layers=12,
                                d_model=640, n_heads=10, n_kv_heads=5,
                                d_ff=2560, vocab=50048, head_dim=64),
                 batch=32, seq=512, steps=300),
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="ci", choices=list(PRESETS))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--fresh", action="store_true")
    args = ap.parse_args()
    p = PRESETS[args.preset]
    cfg: ArchConfig = p["cfg"]
    if args.fresh:
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    model = Model(cfg)
    print(f"model {cfg.name}: {model.param_count()/1e6:.1f}M params")
    data = DataConfig(vocab=cfg.vocab, seq_len=p["seq"],
                      global_batch=p["batch"], seed=0)
    hist = train(
        model, data,
        TrainConfig(microbatches=2,
                    opt=OptConfig(lr=1e-3, warmup_steps=20,
                                  decay_steps=p["steps"])),
        LoopConfig(total_steps=p["steps"], ckpt_every=50, log_every=10,
                   ckpt_dir=args.ckpt_dir))
    print(f"loss: {hist['loss'][0]:.3f} -> {hist['loss'][-1]:.3f} "
          f"({len(hist['loss'])} steps, "
          f"{1e3 * sum(hist['step_time'])/len(hist['step_time']):.0f} "
          f"ms/step)")


if __name__ == "__main__":
    main()
