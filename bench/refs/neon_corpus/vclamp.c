/* XNNPACK-style f32 clamp (vrelu with both bounds): scalar bounds are
 * broadcast once, the strip loop is pure vmax/vmin. */
#include <arm_neon.h>

void xnn_f32_vclamp_ukernel(size_t n, const float* x, float* y,
                            float output_min, float output_max) {
  const float32x4_t vmin = vdupq_n_f32(output_min);
  const float32x4_t vmax = vdupq_n_f32(output_max);
  for (; n >= 4; n -= 4) {
    float32x4_t vacc = vld1q_f32(x); x += 4;
    vacc = vmaxq_f32(vacc, vmin);
    vacc = vminq_f32(vacc, vmax);
    vst1q_f32(y, vacc); y += 4;
  }
  for (; n != 0; n -= 1) {
    float vx = *x; x += 1;
    vx = vx < output_min ? output_min : vx;
    vx = vx > output_max ? output_max : vx;
    *y = vx; y += 1;
  }
}
