from repro.data.synthetic import (make_cifar_like, make_packed_tokens,
                                  make_token_dataset, cnn_task, lm_task,
                                  mlp_task)
from repro.data.partition import partition_iid, partition_dirichlet
from repro.data.loader import batch_dataset, client_batches

__all__ = ["make_cifar_like", "make_packed_tokens", "make_token_dataset",
           "cnn_task", "lm_task", "mlp_task",
           "partition_iid", "partition_dirichlet", "batch_dataset",
           "client_batches"]
