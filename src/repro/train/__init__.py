"""repro.train — streamed on-device walk→SGNS training (DESIGN.md §14).

    from repro.train import StreamingSGNSTrainer, train_streamed

    trainer = StreamingSGNSTrainer(vocab=g.n, dim=64, window=10)
    emb, stats = trainer.train(runner.rounds())   # trains k-1 while k walks
"""
from repro.train.pairs import device_negatives, device_pairs, num_pairs
from repro.train.shard import (init_sharded_params, pow2_bucket,
                               shard_opt_state, table_rows,
                               train_epoch_sharded)
from repro.train.stats import TrainRecorder, TrainStats
from repro.train.stream import StreamingSGNSTrainer, train_streamed

__all__ = [
    "StreamingSGNSTrainer", "TrainRecorder", "TrainStats",
    "device_negatives", "device_pairs", "init_sharded_params", "num_pairs",
    "pow2_bucket", "shard_opt_state", "table_rows", "train_epoch_sharded",
    "train_streamed",
]
