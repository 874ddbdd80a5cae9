"""Uniform coordinates: i.i.d. in every mode. Repeated coordinates stay
as separate COO entries, which every consumer sums. The mix's
``coords`` takes no parameters."""
import jax


def coords(key, dims, nnz: int, spec: dict):
    """One int32 column of ``nnz`` coordinates per mode."""
    return [jax.random.randint(k, (nnz,), 0, I) for k, I in
            zip(jax.random.split(key, len(dims)), dims)]
