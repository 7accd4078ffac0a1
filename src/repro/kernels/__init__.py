# Pallas kernels for the OLTP hot-spots, each with an exact oracle in ref.py
# and a jit'd public wrapper in ops.py (interpret mode off-TPU):
#   scatter_max.py      — SSN-guarded scatter-max (recovery §5 batch replay)
#   batch_occ.py        — segmented max/min reduce (batched OCC §4.2/§4.4)
#   bucketing.py        — power-of-two bucket ladder that bounds the jit cache
