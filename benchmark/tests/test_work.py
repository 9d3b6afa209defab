"""Needed-bytes functions against hand arithmetic."""

from lib import work

MIB = 1 << 20


def test_hdfs_stripe_by_hand():
    # RS(10,4), 10 MiB stripe: 10 data + 4 parity shards of 1 MiB.
    assert work.encode_bytes(10 * MIB, 10, 14) == 14 * MIB
    # 4 rebuilt data shards from 10 survivors: 14 shards of 1 MiB.
    assert work.reconstruct_bytes(10 * MIB, 10, 4) == 14 * MIB
    # a 64 MiB file: 6 full stripes and one of 4 MiB (419,431-byte shards)
    assert work.stripe_payloads(64 * MIB, 10 * MIB) == [10 * MIB] * 6 + [
        4 * MIB]
    assert work.put_bytes_needed([64 * MIB], 10 * MIB, 10, 14) == (
        6 * 14 * MIB + 14 * 419431)


def test_minio_stripe_by_hand():
    # RS(12,4), capacity 12 x 87,382 = 1,048,584 bytes: MinIO's shards.
    assert work.shard_bytes(1048584, 12) == 87382
    assert work.encode_bytes(1048584, 12, 16) == 16 * 87382
    # 10 MiB object: 9 full stripes and one of 1,048,504 bytes, whose
    # shards are ceil(1,048,504 / 12) = 87,376 bytes
    assert work.stripe_payloads(10 * MIB, 1048584) == [1048584] * 9 + [
        1048504]
    assert work.put_bytes_needed([10 * MIB], 1048584, 12, 16) == (
        9 * 16 * 87382 + 16 * 87376)


def test_overlapping_reads_of_one_stripe_count_once():
    one = work.reconstruct_bytes(10 * MIB, 10, 4)
    reads = [(("o", 1), 0.0, 1.0, 10 * MIB), (("o", 1), 0.5, 1.5, 10 * MIB),
             (("o", 1), 2.0, 3.0, 10 * MIB), (("o", 2), 0.5, 0.7, 10 * MIB)]
    assert work.degraded_read_bytes_needed(reads, 10, 4) == 3 * one
