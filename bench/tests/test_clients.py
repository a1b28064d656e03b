"""The benchmark's own client data: sizes from the traffic file, token rows
from the seed."""
import numpy as np
import pytest

from bench import clients, spec


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11, 2 ** 63 + 5])
def test_token_rows_in_bulk_match_row_by_row(seed):
    samples = np.array([[0, 7], [3, 3], [12, 1]])
    rows = clients.token_rows(seed, 5, samples, 1000, 16)
    assert rows.shape == (3, 2, 17) and rows.dtype == np.int32
    for i, j in np.ndindex(samples.shape):
        one = clients.token_rows(seed, 5, np.array([samples[i, j]]), 1000, 16)[0]
        np.testing.assert_array_equal(rows[i, j], one)
    np.testing.assert_array_equal(rows[1, 0], rows[1, 1])
    assert rows.min() >= 5 and rows.max() < 1000


def test_token_rows_differ_by_seed_client_and_sample():
    a = clients.token_rows(3, 1, np.array([0, 1]), 151936, 512)
    assert (a[0] != a[1]).mean() > 0.99
    assert (a != clients.token_rows(4, 1, np.array([0, 1]), 151936, 512)).mean() > 0.99
    assert (a != clients.token_rows(3, 2, np.array([0, 1]), 151936, 512)).mean() > 0.99


def test_lognormal_cell_sizes_are_what_its_traffic_file_says():
    """xdev-lognormal: LEAF Shakespeare's mean and CV in 512-token sequences,
    read at 32 stratum midpoints: mean 7.28, CV 1.21, K_max 23, mean K 3.81."""
    cell = spec.load_cell("qwen05b-xdev-lognormal")
    s = clients.client_sizes(cell.traffic["clients"], cell.traffic["fl"]["num_clients"])
    assert s.mean() == pytest.approx(7.28125) and s.std() / s.mean() == pytest.approx(1.2126, abs=1e-4)
    k = -(-s // cell.traffic["fl"]["local_batch"])
    assert k.max() == 23 and k.mean() == pytest.approx(3.8125) and s.min() == 2


def test_equal_sizes():
    np.testing.assert_array_equal(clients.client_sizes({"sizes": "equal", "mean": 8}, 4),
                                  [8, 8, 8, 8])
    with pytest.raises(ValueError):
        clients.client_sizes({"sizes": "zipf", "mean": 8}, 4)
