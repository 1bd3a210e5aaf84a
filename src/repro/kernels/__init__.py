"""Pallas TPU kernels for the paper's compute hot-spots, and their oracles.

The engine runs the pure-jnp ops of ``ref.py``; its ADC is a one-hot
select on TPU and a gather elsewhere (``ref.adc_distance``).  The
three Pallas kernels are standalone code, checked against those oracles
in interpret mode; none compiles for TPU v5e yet, so none is on the
engine's path:

  pq_adc     — ADC LUT distance (traversal's per-hop examination)
  rerank_l2  — grouped exact-L2 rerank = CASR's pipelined compute stage
  topk_pool  — explored-pool merge (partial top-k without sort)
"""
