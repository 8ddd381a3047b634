// Serial simulated-annealing placer — the CPU measurement baseline.
//
// An independent C++ implementation of the classic VPR annealing loop
// (semantics of vpr/SRC/place/place.c:310 try_place / :246 try_swap /
// :265 update_t: linear-congestion bounding-box cost with the
// crossing-count correction, adaptive range limit, success-ratio
// temperature schedule), written move-at-a-time the way a serial CPU
// does it.  BASELINE.md's first metric is SA moves/sec/chip; the TPU
// placer's batched parallel moves are measured against this binary's
// throughput on the identical netlist, cost function, and schedule.
//
// Deliberately self-contained (no Python/JAX types): the caller passes
// flat arrays through ctypes.  Not a translation of place.c — different
// data layout (ELL nets), different move bookkeeping (per-net bb
// recompute), same annealing semantics.

#include <cmath>
#include <cstdint>
#include <cstdlib>
#include <cstring>

namespace {

struct Tables {
  const int32_t* net_blk;   // [NN, P] driver + sink blocks, -1 pad
  const float* net_q;       // [NN] crossing factor
  const int32_t* blk_net;   // [NB, F] nets of each block, -1 pad
  const uint8_t* is_io;     // [NB]
  const int32_t* ring_xy;   // [NRING, 2]
  int32_t NN, P, NB, F, NRING, nx, ny, io_cap;
};

struct State {
  int32_t* pos;      // [NB, 3]
  int32_t* ring;     // [NB] ring index or -1
  int32_t* occ;      // [NS] occupant block or -1
  double* net_cost;  // [NN]
};

// xorshift128+ — deterministic, fast
struct Rng {
  uint64_t s0, s1;
  explicit Rng(uint64_t seed) {
    s0 = seed * 0x9E3779B97F4A7C15ull + 1;
    s1 = (seed ^ 0xDEADBEEFCAFEBABEull) | 1;
    for (int i = 0; i < 8; i++) next();
  }
  uint64_t next() {
    uint64_t x = s0, y = s1;
    s0 = y;
    x ^= x << 23;
    s1 = x ^ y ^ (x >> 17) ^ (y >> 26);
    return s1 + y;
  }
  double uniform() { return (next() >> 11) * (1.0 / 9007199254740992.0); }
  int32_t below(int32_t n) { return (int32_t)(next() % (uint64_t)n); }
};

inline int32_t site_of(const Tables& t, const int32_t* p, int32_t ring) {
  if (ring >= 0) return t.nx * t.ny + ring * t.io_cap + p[2];
  return (p[1] - 1) * t.nx + (p[0] - 1);
}

double one_net_cost(const Tables& t, const State& st, int32_t n) {
  const int32_t* row = t.net_blk + (int64_t)n * t.P;
  int32_t xmin = 1 << 30, xmax = -(1 << 30), ymin = 1 << 30,
          ymax = -(1 << 30);
  for (int32_t k = 0; k < t.P; k++) {
    int32_t b = row[k];
    if (b < 0) break;
    int32_t x = st.pos[b * 3], y = st.pos[b * 3 + 1];
    if (x < xmin) xmin = x;
    if (x > xmax) xmax = x;
    if (y < ymin) ymin = y;
    if (y > ymax) ymax = y;
  }
  if (xmax < xmin) return 0.0;
  return (double)t.net_q[n] * ((xmax - xmin + 1) + (ymax - ymin + 1));
}

double total_cost(const Tables& t, const State& st) {
  double c = 0;
  for (int32_t n = 0; n < t.NN; n++) {
    st.net_cost[n] = one_net_cost(t, st, n);
    c += st.net_cost[n];
  }
  return c;
}

// delta cost of moving block b (and occupant o of the target site, if
// any, to b's old place): recompute every net touching either block
double swap_delta(const Tables& t, const State& st, int32_t b, int32_t o,
                  double* scratch, int32_t* touched, int32_t* ntouched) {
  int32_t cnt = 0;
  const int32_t* rb = t.blk_net + (int64_t)b * t.F;
  for (int32_t k = 0; k < t.F && rb[k] >= 0; k++) touched[cnt++] = rb[k];
  if (o >= 0) {
    const int32_t* ro = t.blk_net + (int64_t)o * t.F;
    for (int32_t k = 0; k < t.F && ro[k] >= 0; k++) {
      int32_t n = ro[k];
      bool dup = false;
      for (int32_t j = 0; j < cnt; j++)
        if (touched[j] == n) { dup = true; break; }
      if (!dup) touched[cnt++] = n;
    }
  }
  double d = 0;
  for (int32_t j = 0; j < cnt; j++) {
    scratch[j] = one_net_cost(t, st, touched[j]);
    d += scratch[j] - st.net_cost[touched[j]];
  }
  *ntouched = cnt;
  return d;
}

}  // namespace

extern "C" {

// Runs the full anneal.  Returns total proposed moves; fills
// out_stats = {accepted, final_cost, num_temperatures}.
int64_t serial_sa_place(
    // tables.  Sites by block TYPE: type_id [NB] is a block's interior
    // type (-1 for I/O), col_snap [T, nx + 2] the nearest column of a
    // type, type_h [T] the rows a block of it occupies (its anchors are
    // the rows 1 + k * type_h: a RAM is 6 rows tall)
    const int32_t* net_blk, const float* net_q, const int32_t* blk_net,
    const uint8_t* is_io, const int32_t* ring_xy, const int32_t* type_id,
    const int32_t* col_snap, const int32_t* type_h, int32_t NN, int32_t P,
    int32_t NB, int32_t F, int32_t NRING, int32_t nx, int32_t ny,
    int32_t io_cap,
    // state (modified in place)
    int32_t* pos, int32_t* ring, int32_t* occ,
    // schedule
    double inner_num, double exit_t_frac, int32_t max_temps,
    uint64_t seed,
    // out
    double* out_stats) {
  Tables t{net_blk, net_q, blk_net, is_io, ring_xy,
           NN, P, NB, F, NRING, nx, ny, io_cap};
  double* net_cost = (double*)malloc(sizeof(double) * NN);
  State st{pos, ring, occ, net_cost};
  double cost = total_cost(t, st);

  double* scratch = (double*)malloc(sizeof(double) * 2 * F);
  int32_t* touched = (int32_t*)malloc(sizeof(int32_t) * 2 * F);
  Rng rng(seed);

  int64_t proposed = 0, accepted = 0;
  int64_t moves_per_temp =
      (int64_t)(inner_num * pow((double)NB, 4.0 / 3.0)) + 1;

  // starting temperature: std-dev of random-move deltas (place.c:506)
  double rlim = (double)(nx > ny ? nx : ny);
  double sum = 0, sq = 0;
  int64_t nsamp = 0;

  auto propose_apply = [&](double tT, double rl, bool measure) {
    int32_t b = rng.below(NB);
    int32_t np[3];
    int32_t nring = -1;
    int32_t irl = (int32_t)rl;
    if (irl < 1) irl = 1;
    if (is_io[b]) {
      nring = (ring[b] + (rng.below(4 * irl + 1) - 2 * irl) + NRING) % NRING;
      np[0] = ring_xy[nring * 2];
      np[1] = ring_xy[nring * 2 + 1];
      np[2] = rng.below(io_cap);
    } else {
      np[0] = pos[b * 3] + rng.below(2 * irl + 1) - irl;
      int32_t dy = rng.below(2 * irl + 1) - irl;
      if (np[0] < 1) np[0] = 1;
      if (np[0] > nx) np[0] = nx;
      // onto a site of the block's own type: the nearest column of
      // the type, and dy tiles up or down counted in whole blocks (a
      // block of height 1: the column as drawn, the row clipped)
      const int32_t ty = type_id[b], h = type_h[ty];
      np[0] = col_snap[ty * (nx + 2) + np[0]];
      int32_t k = (pos[b * 3 + 1] - 1) / h
          + (dy < 0 ? -((-dy + h - 1) / h) : (dy + h - 1) / h);
      if (k < 0) k = 0;
      if (k > ny / h - 1) k = ny / h - 1;
      np[1] = 1 + k * h;
      np[2] = 0;
    }
    int32_t src = site_of(t, pos + b * 3, ring[b]);
    int32_t dst = site_of(t, np, nring);
    if (src == dst) return;
    int32_t o = occ[dst];
    if (o >= 0 && type_id[o] != type_id[b]) return;  // type clash
    proposed++;
    // tentatively apply
    int32_t oldp[3] = {pos[b * 3], pos[b * 3 + 1], pos[b * 3 + 2]};
    int32_t oldr = ring[b];
    pos[b * 3] = np[0]; pos[b * 3 + 1] = np[1]; pos[b * 3 + 2] = np[2];
    ring[b] = nring;
    if (o >= 0) {    // occupant swaps into b's old site
      pos[o * 3] = oldp[0]; pos[o * 3 + 1] = oldp[1];
      pos[o * 3 + 2] = oldp[2];
      ring[o] = oldr;
    }
    int32_t cnt = 0;
    double d = swap_delta(t, st, b, o, scratch, touched, &cnt);
    if (measure) { sum += d; sq += d * d; nsamp++; }
    bool acc = d <= 0 || rng.uniform() < exp(-d / (tT > 1e-30 ? tT : 1e-30));
    if (acc) {
      accepted++;
      cost += d;
      for (int32_t j = 0; j < cnt; j++) st.net_cost[touched[j]] = scratch[j];
      occ[src] = o;
      occ[dst] = b;
    } else {
      pos[b * 3] = oldp[0]; pos[b * 3 + 1] = oldp[1];
      pos[b * 3 + 2] = oldp[2];
      ring[b] = oldr;
      if (o >= 0) {   // occupant returns to its original (dst) site
        pos[o * 3] = np[0]; pos[o * 3 + 1] = np[1]; pos[o * 3 + 2] = np[2];
        ring[o] = nring;
      }
    }
  };

  // sample at infinite temperature for t0 (accept-all)
  for (int32_t i = 0; i < 256; i++) propose_apply(1e30, rlim, true);
  double var = nsamp ? sq / nsamp - (sum / nsamp) * (sum / nsamp) : 1.0;
  double T = 20.0 * sqrt(var > 1e-12 ? var : 1e-12);

  int32_t temps = 0;
  for (; temps < max_temps; temps++) {
    int64_t acc0 = accepted, prop0 = proposed;
    for (int64_t m = 0; m < moves_per_temp; m++)
      propose_apply(T, rlim, false);
    double srat = proposed > prop0
        ? (double)(accepted - acc0) / (double)(proposed - prop0) : 0.0;
    if (srat > 0.96) T *= 0.5;
    else if (srat > 0.8) T *= 0.9;
    else if (srat > 0.15 || rlim > 1.0) T *= 0.95;
    else T *= 0.8;
    double nrl = rlim * (1.0 - 0.44 + srat);
    rlim = nrl < 1.0 ? 1.0 : (nrl > (double)(nx > ny ? nx : ny)
                              ? (double)(nx > ny ? nx : ny) : nrl);
    if (T < exit_t_frac * cost / (NN > 0 ? NN : 1)) break;
  }
  // quench
  for (int64_t m = 0; m < moves_per_temp; m++)
    propose_apply(0.0, 1.0, false);

  out_stats[0] = (double)accepted;
  out_stats[1] = total_cost(t, st);
  out_stats[2] = (double)temps;
  free(net_cost);
  free(scratch);
  free(touched);
  return proposed;
}
}
