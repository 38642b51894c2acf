"""Operations and bytes of one spin-image call, as the algorithm needs
them (not as the kernel's one-hot matmul spends them).

Per (cloud point, oriented point) pair, in float32:
  d = x - p                               3
  beta = n . d                            5  (3 mul, 2 add)
  r2 = d . d                              5
  alpha = sqrt(r2 - beta^2)               3  (mul, sub, sqrt)
  alpha bin = floor(alpha * n_a / a_max)  2
  beta bin = floor((beta + b_max) * ..)   3
  bounds check                            3  (alpha < a_max, |beta| < b_max)
  bin increment                           1
                                         --
                                         25
Bytes: the cloud, the call's centers and normals read once, its
histograms written once.
"""

OPS_PER_PAIR = 25


def call(cfg: dict, start: int, stop: int, reference) -> tuple[float, float]:
    k = stop - start
    pairs = k * cfg["cloud_n"]
    nbytes = 4 * (3 * cfg["cloud_n"] + 6 * k
                  + k * cfg["n_alpha"] * cfg["n_beta"])
    return float(OPS_PER_PAIR * pairs), float(nbytes)
