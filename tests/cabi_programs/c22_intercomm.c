/* Communicator-construction closure:
 * Cart_sub (every 2-D decomposition textbook), Intercomm_create /
 * Intercomm_merge, Comm_create_group, Grequest_start/complete.
 * References: ompi/mpi/c/cart_sub.c.in, intercomm_create.c.in,
 * intercomm_merge.c.in, comm_create_group.c.in,
 * grequest_start.c.in. */
#include <mpi.h>
#include <stdio.h>
#include <stdlib.h>

static int rank, size;

#define CHECK(cond, code)                                            \
    do {                                                             \
        if (!(cond)) {                                               \
            fprintf(stderr, "rank %d: check failed at line %d\n",    \
                    rank, __LINE__);                                 \
            MPI_Abort(MPI_COMM_WORLD, code);                         \
        }                                                            \
    } while (0)

/* generalized-request callbacks */
static int g_query_calls;
static int query_fn(void *extra, MPI_Status *st)
{
    g_query_calls++;
    MPI_Status_set_elements(st, MPI_INT, *(int *)extra);
    MPI_Status_set_cancelled(st, 0);
    st->MPI_SOURCE = MPI_UNDEFINED;
    st->MPI_TAG = MPI_UNDEFINED;
    return MPI_SUCCESS;
}
static int free_calls;
static int free_fn(void *extra)
{
    (void)extra;
    free_calls++;
    return MPI_SUCCESS;
}
static int cancel_fn(void *extra, int complete)
{
    (void)extra;
    (void)complete;
    return MPI_SUCCESS;
}

int main(int argc, char **argv)
{
    MPI_Init(&argc, &argv);
    MPI_Comm_rank(MPI_COMM_WORLD, &rank);
    MPI_Comm_size(MPI_COMM_WORLD, &size);
    CHECK(size >= 4, 1);

    /* ---- Cart_sub: 2 x (size/2) grid -> row and column comms ---- */
    {
        int dims[2] = {2, size / 2};
        int periods[2] = {0, 0};
        MPI_Comm cart;
        CHECK(MPI_Cart_create(MPI_COMM_WORLD, 2, dims, periods, 0,
                              &cart) == MPI_SUCCESS, 2);
        if (cart != MPI_COMM_NULL) {
            int coords[2];
            MPI_Cart_coords(cart, rank, 2, coords);

            int keep_cols[2] = {0, 1};   /* rows: vary dim 1 */
            MPI_Comm row;
            CHECK(MPI_Cart_sub(cart, keep_cols, &row) == MPI_SUCCESS,
                  3);
            int rsz = -1, rrk = -1;
            MPI_Comm_size(row, &rsz);
            MPI_Comm_rank(row, &rrk);
            CHECK(rsz == size / 2 && rrk == coords[1], 4);
            /* the row comm keeps cartesian topology in 1-D */
            int nd = -1;
            MPI_Cartdim_get(row, &nd);
            CHECK(nd == 1, 5);
            /* sum of coords[0] over my row == my row index * rowsize */
            int mine = coords[0], tot = -1;
            MPI_Allreduce(&mine, &tot, 1, MPI_INT, MPI_SUM, row);
            CHECK(tot == coords[0] * rsz, 6);

            int keep_rows[2] = {1, 0};   /* columns: vary dim 0 */
            MPI_Comm col;
            CHECK(MPI_Cart_sub(cart, keep_rows, &col) == MPI_SUCCESS,
                  7);
            int csz = -1, crk = -1;
            MPI_Comm_size(col, &csz);
            MPI_Comm_rank(col, &crk);
            CHECK(csz == 2 && crk == coords[0], 8);
            MPI_Comm_free(&row);
            MPI_Comm_free(&col);
            MPI_Comm_free(&cart);
        }
    }

    /* ---- Intercomm_create from two halves, then merge ----------- */
    {
        int half = size / 2;
        int in_low = rank < half;
        MPI_Comm local;
        MPI_Comm_split(MPI_COMM_WORLD, in_low ? 0 : 1, rank, &local);

        /* leaders: rank 0 of each half; peer comm is WORLD */
        MPI_Comm inter;
        CHECK(MPI_Intercomm_create(local, 0, MPI_COMM_WORLD,
                                   in_low ? half : 0, 99, &inter)
              == MPI_SUCCESS, 9);
        int is_inter = 0;
        MPI_Comm_test_inter(inter, &is_inter);
        CHECK(is_inter, 10);
        int rsize = -1;
        MPI_Comm_remote_size(inter, &rsize);
        CHECK(rsize == (in_low ? size - half : half), 11);

        /* cross-group pt2pt: local rank i <-> remote rank i */
        int lr = -1;
        MPI_Comm_rank(inter, &lr);
        if (lr < rsize) {
            int v = 1000 + rank, got = -1;
            MPI_Sendrecv(&v, 1, MPI_INT, lr, 5, &got, 1, MPI_INT, lr,
                         5, inter, MPI_STATUS_IGNORE);
            CHECK(got == 1000 + (in_low ? half + lr : lr), 12);
        }

        /* merge: low group first when high=0 at the low side */
        MPI_Comm flat;
        CHECK(MPI_Intercomm_merge(inter, in_low ? 0 : 1, &flat)
              == MPI_SUCCESS, 13);
        int fsz = -1, frk = -1;
        MPI_Comm_size(flat, &fsz);
        MPI_Comm_rank(flat, &frk);
        CHECK(fsz == size, 14);
        CHECK(frk == rank, 15);          /* low kept first, order kept */
        int one = 1, tot = 0;
        MPI_Allreduce(&one, &tot, 1, MPI_INT, MPI_SUM, flat);
        CHECK(tot == size, 16);
        MPI_Comm_free(&flat);
        MPI_Comm_free(&inter);
        MPI_Comm_free(&local);
    }

    /* ---- Comm_create_group: collective over the GROUP only ------ */
    {
        MPI_Group wg, evens;
        MPI_Comm_group(MPI_COMM_WORLD, &wg);
        int n_even = (size + 1) / 2;
        int *er = malloc(n_even * sizeof(int));
        for (int i = 0; i < n_even; i++)
            er[i] = 2 * i;
        MPI_Group_incl(wg, n_even, er, &evens);
        free(er);
        if (rank % 2 == 0) {
            MPI_Comm ec;
            CHECK(MPI_Comm_create_group(MPI_COMM_WORLD, evens, 77, &ec)
                  == MPI_SUCCESS, 17);
            CHECK(ec != MPI_COMM_NULL, 18);
            int esz = -1, erk = -1;
            MPI_Comm_size(ec, &esz);
            MPI_Comm_rank(ec, &erk);
            CHECK(esz == n_even && erk == rank / 2, 19);
            int one = 1, tot = 0;
            MPI_Allreduce(&one, &tot, 1, MPI_INT, MPI_SUM, ec);
            CHECK(tot == n_even, 20);
            MPI_Comm_free(&ec);
        }
        /* odd ranks never call it — that is the point of the group-
         * collective semantics (comm_create would deadlock here) */
        MPI_Group_free(&wg);
        MPI_Group_free(&evens);
    }

    /* ---- generalized requests ----------------------------------- */
    {
        int elems = 7;
        MPI_Request gr;
        CHECK(MPI_Grequest_start(query_fn, free_fn, cancel_fn, &elems,
                                 &gr) == MPI_SUCCESS, 21);
        int flag = 99;
        MPI_Status st;
        MPI_Test(&gr, &flag, &st);
        CHECK(flag == 0, 22);            /* not complete yet */
        CHECK(MPI_Grequest_complete(gr) == MPI_SUCCESS, 23);
        MPI_Wait(&gr, &st);
        CHECK(g_query_calls >= 1, 24);
        CHECK(free_calls == 1, 25);
        int cnt = -1;
        MPI_Get_count(&st, MPI_INT, &cnt);
        CHECK(cnt == elems, 26);
        CHECK(gr == MPI_REQUEST_NULL, 27);
    }

    MPI_Barrier(MPI_COMM_WORLD);
    printf("OK c22_intercomm rank=%d/%d\n", rank, size);
    MPI_Finalize();
    return 0;
}
