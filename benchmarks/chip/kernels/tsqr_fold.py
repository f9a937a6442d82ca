"""Operations of one streaming-TSQR fold (``core/tsqr.py`` of the program):
the R factor of a Householder QR of an ``m x n`` matrix, ``m >= n``,
costs ``2 m n^2 - 2 n^3 / 3`` operations. A fold stacks the running
``n x n`` R on a chunk of ``rows`` activation rows, so ``m = n + rows``;
the first fold of a stream has no R yet, ``m = rows``.
"""


def qr_flops(m: int, n: int) -> float:
    return 2.0 * m * n * n - 2.0 * n ** 3 / 3.0


def fold_flops(n: int, rows: int, first: bool = False) -> float:
    return qr_flops(rows if first else n + rows, n)


def batch_flops(widths, tokens: int, fold_rows: int) -> float:
    """Every fold of one batch of ``tokens`` rows into R factors of the
    given widths, ``fold_rows`` rows at a time, each R already started."""
    total = 0.0
    for n in widths:
        left = tokens
        while left > 0:
            total += fold_flops(n, min(fold_rows, left))
            left -= fold_rows
    return total
