"""Small sizes at which a whole run fits a CPU test (Pallas interpret mode)."""

SMALL = {
    "fopo_train": dict(num_items=3000, embed_dim=16, batch_size=8, num_samples=64,
                       top_k=32, num_clusters=32, n_probe=4, cap_tile=32,
                       sample_tile=8, num_users=64, num_positives=8),
    "recsys_serve": dict(item_vocab=4000, embed_dim=16, seq_len=12, num_clusters=32,
                         n_probe=4),
}
SMALL_TRAFFIC = {
    "train_job": dict(chunk_steps=2, trace_steps=2),
    "open_loop": dict(rate=60, warmup_batches=2, trace_seconds=1.0),
}
CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

