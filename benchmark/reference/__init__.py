"""The plain reference: the DiffusionViT forward, the DDIM update, the
smooth-L1 loss with its gradients, and clip + AdamW with a cosine schedule, in
straightforward float32 ``jax.numpy`` under matmul precision ``highest``.

Nothing here imports the program (``ddim_cold_tpu``) or takes anything the
program has made: the weights come from ``benchmark/weights.py``, the inputs
from the seed. It follows the upstream description (ViT.py:158-237,
multi_gpu_trainer.py:89-134, diffusion_loader.py:60-97 of the reference
project); departures are noted where they are made.
"""
