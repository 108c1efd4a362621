# Pallas TPU kernels for the perf-critical compute hot spots:
#   flash_attention.py — online-softmax blocked attention (causal/local,
#                        GQA via ops wrapper); the TPU path for model-zoo
#                        prefill/train attention and the GDP placer.
#   segment_maxpool.py — GraphSAGE neighbor max aggregation as blocked
#                        masked-adjacency max (TPU-native; DESIGN.md §3).
#   band_attention.py  — block-sparse banded attention (segmented TF pass).
#   csr_maxpool.py     — BSR-blocked neighbor max-pool (scalar-prefetched
#                        tile ids; bytes scale with edges).
# ops.py = jit'd dispatch wrappers (interpret mode off-TPU: ops.interpret);
# ref.py = pure-jnp oracles anchoring tests/test_kernels.py.
