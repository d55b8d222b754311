"""repro: production-scale JAX/Pallas reproduction of REGTOP-k
(Novel Gradient Sparsification Algorithm via Bayesian Inference)."""
