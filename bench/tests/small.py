"""Small sizes of the cells for CPU tests: the same files and code paths,
with every width and count shrunk so that a run takes seconds."""

SMALL_MODEL = {"hidden_size": 256, "intermediate_size": 768,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "head_dim": 64, "vocab_size": 1024}
SMALL_TRAFFIC = {"batch_size": 2, "seq_len": 128, "bottleneck_dim": 8}


def shrink(files: dict) -> None:
    """Override hook for ``bench.run.main``: shrink the cell in place."""
    files["config"]["model"].update(SMALL_MODEL)
    t = files["traffic"]
    t.update(SMALL_TRAFFIC)
    t["ticks_per_epoch"] = min(t["ticks_per_epoch"], 6)
