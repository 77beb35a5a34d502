"""What the input layer costs the step loop: the mean, in ms, of the window's
`train.input_wait` spans in the program's span ring, one per step: the time
the prepared `DataLoaderShard` took to hand over one device batch
(`device_put` included). Host clock; needs no trace. The driver's one loader
is iterated again and again from its first batch, first by the set-up's
`reference_steps` steps and from then on by the window, and every span carries
its batch's place in the epoch: where the ring's spans do not count through
the epochs from the first batch on, something else went through a prepared
loader in this process and nothing is reported."""

import program_spans


def read(run):
    cell = run["cell"]
    waits = program_spans.ring_spans("train.input_wait")
    if cell.rehearsal or not waits:
        return None
    epoch = int(cell.traffic["host_batches"])
    places = [attrs.get("batch") for *_, attrs in waits]
    if places != [i % epoch for i in range(len(places))]:
        print(f"input wait: {len(places)} spans do not count through epochs of {epoch} batches "
              f"from the first on (they start {places[:epoch + 2]}): not one loader's", flush=True)
        return None
    waits = waits[int(cell.traffic["reference_steps"]):]
    if not waits:
        return None
    lengths = sorted(1e3 * (end - start) for _, start, end, _, _ in waits)
    print(f"input wait: {len(lengths)} steps, median {lengths[len(lengths) // 2]:.3f} ms "
          f"max {lengths[-1]:.3f} ms", flush=True)
    return sum(lengths) / len(lengths)
