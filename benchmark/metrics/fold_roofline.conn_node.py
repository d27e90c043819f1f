"""The fold's share of its roofline in the conn_node cell, in percent,
read as fold_roofline.py reads it: the rows each traced query covered
times conn_stats' narrowest lossless bits a row, over the peak HBM
bandwidth, over the device time inside the queries' spans."""

from benchmark.metrics.fold_roofline import read  # noqa: F401
