"""TPU-native gradient-boosting training & serving container.

A ground-up JAX/XLA re-design of the SageMaker XGBoost container: the same
train/serve contracts (SM_* env, channel/HP validation, HPO stdout metrics,
checkpoint/resume, selectable inference) over an XLA histogram tree builder
sharded across a TPU mesh instead of libxgboost + Rabit/NCCL.
"""

__version__ = "0.1.0"
