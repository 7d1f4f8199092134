"""Model FLOPs from shapes: what the forward and backward passes of a layer
require, recomputation not counted.  A multiply-accumulate is 2 FLOPs; the
backward pass of a matmul or convolution costs two more of the same size
(input gradient and weight gradient), one where the layer's input needs no
gradient (`first`)."""


class Count:
    def __init__(self):
        self.forward_macs = 0
        self.train_flops = 0
        self.param_bytes_f32 = 0

    def _add(self, macs, params, first):
        self.forward_macs += macs
        self.train_flops += 2 * macs * (2 if first else 3)
        self.param_bytes_f32 += 4 * params

    def conv(self, cin, cout, k, h_out, w_out, first=False):
        self._add(cin * cout * k * k * h_out * w_out, cin * cout * k * k,
                  first)

    def dense(self, n_in, n_out, first=False):
        self._add(n_in * n_out, n_in * n_out, first)
