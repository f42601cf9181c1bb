"""A keyed producer in a dozen lines: records of one entity keep their
order, batching is the client's business.

    python examples/keyed_producer.py 127.0.0.1:9000 topic1
"""
import sys

from ripplemq_tpu.client import ProducerClient

producer = ProducerClient(sys.argv[1].split(","), linger_s=0.001,
                          batch_size=1048576, max_in_flight=5)
waiters = [producer.send(sys.argv[2], b"order %d of user %d" % (i, i % 7),
                         key=b"user-%d" % (i % 7)) for i in range(100)]
for w in waiters:  # send() returned at once; the waiter gives the offset
    print(f"partition {w.partition} offset {w()}")
producer.close()
