from .datasets import sosd_like, DATASETS
