from repro.training.loop import (Trainer, make_chunk_step,
                                 same_decision_runs, train_state_sharding)
from repro.training.steps import (init_train_state, make_eval_step,
                                  make_host_cond_steps, make_train_step,
                                  total_loss, xent_loss)

__all__ = ["Trainer", "init_train_state", "make_chunk_step",
           "make_eval_step", "make_host_cond_steps", "make_train_step",
           "same_decision_runs", "total_loss", "train_state_sharding",
           "xent_loss"]
